"""The daemon binary: config → spawn → wait for signal.

reference: cmd/gubernator/main.go — reconstructed, mount empty.
Usage: python -m gubernator_tpu.cmd.daemon [--config FILE]
(all GUBER_* env vars apply; see config.py).
"""
from __future__ import annotations

import argparse
import logging
import signal
import sys
import threading


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="gubernator-tpu daemon")
    ap.add_argument("--config", default="", help="KEY=value config file")
    ap.add_argument("--grpc", default="", help="override GUBER_GRPC_ADDRESS")
    ap.add_argument("--http", default="", help="override GUBER_HTTP_ADDRESS")
    ap.add_argument("--client", default="",
                    help="override GUBER_CLIENT_ADDRESS (shared "
                         "SO_REUSEPORT front door)")
    args = ap.parse_args(argv)

    from .. import compilecache

    compilecache.setup()  # before jax is imported

    from ..config import setup_daemon_config
    from ..daemon import spawn_daemon

    cfg = setup_daemon_config(conf_file=args.config)
    if args.grpc:
        cfg.grpc_listen_address = args.grpc
    if args.http:
        cfg.http_listen_address = args.http
    if args.client:
        cfg.client_listen_address = args.client
    logging.basicConfig(
        level=getattr(logging, cfg.log_level.upper(), logging.INFO),
        format="%(asctime)s %(name)s %(levelname)s %(message)s")

    d = spawn_daemon(cfg)
    stop = threading.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, lambda *_: stop.set())
    print(f"gubernator-tpu listening grpc={cfg.grpc_listen_address} "
          f"http={cfg.http_listen_address}", flush=True)
    stop.wait()
    d.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
