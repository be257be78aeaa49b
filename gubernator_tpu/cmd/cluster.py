"""Boot a local N-daemon cluster (development tool).

reference: cmd/gubernator-cluster/main.go — reconstructed, mount empty.
Usage: python -m gubernator_tpu.cmd.cluster [--count N] [--base-port P]
       python -m gubernator_tpu.cmd.cluster --group [--client-port P]

--group boots the SO_REUSEPORT front-door shape instead (OS processes
sharing one client port, each with its own engine and GIL —
ARCHITECTURE.md §3.1); without it, daemons run in-process on unique
ports (the functional-test topology).
"""
from __future__ import annotations

import argparse
import signal
import sys
import threading


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="local gubernator-tpu cluster")
    ap.add_argument("--count", type=int, default=4)
    ap.add_argument("--base-port", type=int, default=9080)
    ap.add_argument("--cache-size", type=int, default=1 << 16)
    ap.add_argument("--group", action="store_true",
                    help="SO_REUSEPORT subprocess group sharing one "
                         "client port")
    ap.add_argument("--client-port", type=int, default=0,
                    help="with --group: shared client port "
                         "(0 = OS-assigned)")
    args = ap.parse_args(argv)

    if args.group and args.base_port != ap.get_default("base_port"):
        ap.error("--base-port applies only without --group (group "
                 "workers use OS-assigned peer ports; use --client-port "
                 "for the shared front door)")

    from .. import compilecache

    compilecache.setup()  # before jax is imported; workers inherit it

    def _serve(handle):
        """Install signal handlers only AFTER startup, so Ctrl-C during
        a slow/hung boot still interrupts (KeyboardInterrupt) instead of
        setting an event nothing reads yet."""
        stop = threading.Event()
        for sig in (signal.SIGINT, signal.SIGTERM):
            signal.signal(sig, lambda *_: stop.set())
        stop.wait()
        handle.stop()

    if args.group:
        from ..cluster import start_subprocess_group

        g = start_subprocess_group(args.count, cache_size=args.cache_size,
                                   client_port=args.client_port)
        print(f"group client={g.client_address}", flush=True)
        for i, addr in enumerate(g.grpc_addresses):
            print(f"worker[{i}] peer-grpc={addr} "
                  f"http={g.http_addresses[i]}", flush=True)
        _serve(g)
        return 0

    from ..cluster import start_with
    from ..config import DaemonConfig

    cfgs = [DaemonConfig(
        grpc_listen_address=f"127.0.0.1:{args.base_port + 2 * i}",
        http_listen_address=f"127.0.0.1:{args.base_port + 2 * i + 1}",
        cache_size=args.cache_size) for i in range(args.count)]
    c = start_with(cfgs)
    for i, d in enumerate(c.daemons):
        print(f"daemon[{i}] grpc={d.cfg.grpc_listen_address} "
              f"http={d.cfg.http_listen_address}", flush=True)

    _serve(c)
    return 0


if __name__ == "__main__":
    sys.exit(main())
