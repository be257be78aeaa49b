"""Native host-ops extension tests (skipped when not built)."""
import pytest

native = pytest.importorskip("gubernator_tpu.ops.native")

from gubernator_tpu.hashing import (  # noqa: E402
    fnv1a64,
    hash_key,
    hash_keys,
    hash_request_keys,
)


def test_raw_fnv_matches_python():
    keys = ["", "a", "load_k42", "πδ∞ unicode", "x" * 10_000]
    raw = native.hash_keys(keys)
    for k, h in zip(keys, raw):
        assert int(h) == fnv1a64(k.encode("utf-8"))


def test_pair_hash_equals_joined():
    names = ["svc", "", "a_b"]
    uks = ["user:1", "k", ""]
    assert (native.hash_pairs(names, uks)
            == native.hash_keys([f"{n}_{u}" for n, u in zip(names, uks)])).all()


def test_hash_request_keys_matches_scalar():
    names = [f"n{i}" for i in range(100)]
    uks = [f"u{i}" for i in range(100)]
    batch = hash_request_keys(names, uks)
    for i in range(100):
        assert int(batch[i]) == hash_key(names[i], uks[i])


def test_hash_keys_native_equals_fallback():
    import gubernator_tpu.hashing as H

    keys = [f"mixed_{i}" for i in range(1000)]
    with_native = hash_keys(keys)
    saved, H._native = H._native, None
    try:
        without = hash_keys(keys)
    finally:
        H._native = saved
    assert (with_native == without).all()


def test_errors():
    with pytest.raises(TypeError):
        native.hash_keys([1, 2, 3])
    with pytest.raises(ValueError):
        native.hash_pairs(["a"], ["b", "c"])


# ---- the wire parsers' columns (ISSUE 28: name_hash beside them) --------

def _wire_requests():
    from gubernator_tpu.types import RateLimitRequest

    names = ["acme/x", "globex/y", "plain", "πδ/unicode", "a_b/c_d"]
    return [RateLimitRequest(
        name=names[i % len(names)], unique_key=f"user:{i}", hits=i % 4,
        limit=100 + i, duration=1_000 * (1 + i % 7), algorithm=i % 2,
        behavior=(2 if i % 5 == 0 else 0), burst=(7 if i % 3 == 0 else 0),
        created_at=(1_700_000_000_000 + i if i % 11 == 0 else 0))
        for i in range(300)]


def _parsed_columns(parser):
    """(columns by name, requests, message) from one of the two C++
    passes over the same message."""
    import numpy as np

    from gubernator_tpu.wire import req_to_tlv

    reqs = _wire_requests()
    data = b"".join(req_to_tlv(r) for r in reqs)
    if parser == "parse_get_rate_limits":
        return native.parse_get_rate_limits(data), reqs, data
    # the call's own pair, uninitialised as prepack_wire hands it over
    # (and wider than the message: rows past n must read as padding)
    a64 = np.full((8, 512), -7, np.int64)
    a32 = np.full((3, 512), -7, np.int32)
    n, kh, beh_or, toff, tlen, nh, _derived = native.pack_wire_wave(
        data, 1_700_000_000_999, a64, a32)
    assert not a64[[0, 1, 2, 3, 5, 6, 7], n:].any() and not a32[:, n:].any()
    assert (a64[4, n:] == 1).all() and not a64[5].any()
    cols = {"n": n, "khash": kh, "behavior_or": beh_or,
            "tlv_off": toff, "tlv_len": tlen, "name_hash": nh,
            "hits": a64[1][:n], "limit": a64[2][:n],
            "duration": a64[3][:n], "burst_filled": a64[6][:n],
            "now": a64[7][:n], "behavior": a32[0][:n],
            "algorithm": a32[1][:n], "valid": a32[2][:n]}
    return cols, reqs, data


@pytest.mark.parametrize("parser",
                         ["parse_get_rate_limits", "pack_wire_wave"])
def test_name_hash_is_the_fnv_of_the_name_alone(parser):
    cols, reqs, _ = _parsed_columns(parser)
    assert cols["name_hash"].dtype.itemsize == 8
    assert cols["name_hash"].tolist() == \
        native.hash_keys([r.name for r in reqs]).tolist()
    # what the key hash continues from: name, "_", unique key
    raw = native.hash_pairs([r.name for r in reqs],
                            [r.unique_key for r in reqs])
    if parser == "parse_get_rate_limits":
        assert cols["khash_raw"].tolist() == raw.tolist()
    else:  # the fused ingest returns it mixed, as the table keys it
        from gubernator_tpu.hashing import mix64_np

        assert cols["khash"].tolist() == mix64_np(raw).tolist()


@pytest.mark.parametrize("parser",
                         ["parse_get_rate_limits", "pack_wire_wave"])
def test_older_columns_beside_name_hash_unchanged(parser):
    cols, reqs, data = _parsed_columns(parser)
    n = len(reqs)
    assert cols["n"] == n
    assert cols["hits"].tolist() == [r.hits for r in reqs]
    assert cols["limit"].tolist() == [r.limit for r in reqs]
    assert cols["duration"].tolist() == [r.duration for r in reqs]
    assert cols["algorithm"].tolist() == [int(r.algorithm) for r in reqs]
    assert cols["behavior"].tolist() == [int(r.behavior) for r in reqs]
    assert cols["behavior_or"] == 2
    off, ln = cols["tlv_off"].tolist(), cols["tlv_len"].tolist()
    assert off[0] == 0 and off[-1] + ln[-1] == len(data)
    assert all(off[i] + ln[i] == off[i + 1] for i in range(n - 1))
    if parser == "parse_get_rate_limits":
        assert cols["burst"].tolist() == [r.burst for r in reqs]
        assert cols["created_at"].tolist() == \
            [r.created_at for r in reqs]
        assert set(cols) == {
            "n", "khash_raw", "hits", "limit", "duration", "algorithm",
            "behavior", "burst", "behavior_or", "tlv_off", "tlv_len",
            "created_at", "name_hash"}
    else:
        assert cols["khash"].tolist() == hash_request_keys(
            [r.name for r in reqs],
            [r.unique_key for r in reqs]).tolist()
        assert cols["burst_filled"].tolist() == \
            [r.burst or r.limit for r in reqs]
        assert cols["now"].tolist() == \
            [r.created_at or 1_700_000_000_999 for r in reqs]
        assert cols["valid"].tolist() == [1] * n
