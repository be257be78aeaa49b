"""Config layer tests (reference: config_test.go analog)."""
import pytest

from gubernator_tpu.config import (
    BehaviorConfig,
    DaemonConfig,
    load_conf_file,
    parse_duration_ms,
    parse_peer_list,
    setup_daemon_config,
)


def test_parse_duration_ms():
    assert parse_duration_ms("500ms") == 500
    assert parse_duration_ms("30s") == 30_000
    assert parse_duration_ms("1m30s") == 90_000
    assert parse_duration_ms("2h") == 7_200_000
    assert parse_duration_ms("1.5s") == 1500
    assert parse_duration_ms("100us") == 0  # sub-ms floors
    assert parse_duration_ms(250) == 250
    assert parse_duration_ms("250") == 250
    assert parse_duration_ms("-5s") == -5000
    with pytest.raises(ValueError):
        parse_duration_ms("5 parsecs")
    with pytest.raises(ValueError):
        parse_duration_ms("1s2")


def test_defaults():
    d = setup_daemon_config(env={})
    assert d.grpc_listen_address == "localhost:1051"
    assert d.http_listen_address == "localhost:1050"
    assert d.behaviors.batch_limit == 1000
    assert d.peer_discovery_type == "none"
    assert d.tls is None


def test_env_overrides():
    d = setup_daemon_config(env={
        "GUBER_GRPC_ADDRESS": "0.0.0.0:9990",
        "GUBER_CACHE_SIZE": "1048576",
        "GUBER_BATCH_TIMEOUT": "50ms",
        "GUBER_GLOBAL_SYNC_WAIT": "1s",
        "GUBER_PEERS": "a:1051, b:1051@dc2",
        "GUBER_DATA_CENTER": "dc1",
    })
    assert d.grpc_listen_address == "0.0.0.0:9990"
    assert d.cache_size == 1 << 20
    assert d.behaviors.batch_timeout_ms == 50
    assert d.behaviors.global_sync_wait_ms == 1000
    assert d.peer_discovery_type == "static"
    assert d.static_peers == ["a:1051", "b:1051@dc2"]
    peers = parse_peer_list(d.static_peers, d.data_center)
    assert peers[0].grpc_address == "a:1051"
    assert peers[0].datacenter == "dc1"
    assert peers[1].datacenter == "dc2"


def test_conf_file(tmp_path):
    p = tmp_path / "gubernator.conf"
    p.write_text(
        "# example.conf analog\n"
        "\n"
        "GUBER_GRPC_ADDRESS = 127.0.0.1:7777\n"
        "GUBER_BATCH_LIMIT = 500\n"
    )
    d = setup_daemon_config(conf_file=str(p))
    assert d.grpc_listen_address == "127.0.0.1:7777"
    assert d.behaviors.batch_limit == 500


def test_conf_file_invalid(tmp_path):
    p = tmp_path / "bad.conf"
    p.write_text("not a kv line\n")
    with pytest.raises(ValueError):
        load_conf_file(str(p))


def test_tls_from_env():
    d = setup_daemon_config(env={"GUBER_TLS_AUTO": "true"})
    assert d.tls is not None and d.tls.auto_tls
    d = setup_daemon_config(env={
        "GUBER_TLS_CERT": "/c.pem", "GUBER_TLS_KEY": "/k.pem",
        "GUBER_TLS_CLIENT_AUTH": "verify"})
    assert d.tls.cert_file == "/c.pem"
    assert d.tls.client_auth == "verify"


def test_instance_config_normalizes():
    d = DaemonConfig(cache_size=50_000)
    cfg = d.instance_config()
    assert cfg.cache_size == 1 << 16  # rounded up to power of two
    assert cfg.behaviors is d.behaviors


@pytest.mark.parametrize("ms", [30_000, 1_000, 0])
def test_the_sweep_interval_reaches_the_instance(ms):
    """DaemonConfig → Config: the time between whole-table expiry
    sweeps, and with it how long an insert into a window clogged by
    expired rows can go on failing (no wave sweeps for itself:
    instance._maybe_sweep).  A benchmark configuration's ``rehearsal``
    section sets it so that its 4-s window holds a sweep."""
    assert DaemonConfig().sweep_interval_ms == 30_000
    d = DaemonConfig(sweep_interval_ms=ms)
    assert d.instance_config().sweep_interval_ms == ms
