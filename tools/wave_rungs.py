"""What a daemon's waves carried between two scrapes of ``/metrics``:
rows a wave, device launches and upload slots a wave, the padding of
the rungs they rode, and the share of waves in each rung of the ladder
(ISSUE 49: is the 16,384 rung chosen, is the 32,768 one).

    python tools/wave_rungs.py before.txt after.txt
    python tools/wave_rungs.py http://127.0.0.1:1050/metrics --wait 30
    python tools/wave_rungs.py a.txt b.txt --ladder 1024,8192,16384

A scrape is the page's text, from a file or a URL; one URL with
``--wait S`` is scraped twice, S seconds apart.  Reads
``gubernator_dispatcher_wave_size`` (a dispatcher wave's rows: its
bounds hold the default ladders' rungs, so a wave's class is the rung
ONE launch of it rides on a one-shard daemon),
``gubernator_wave_route_total`` (device launches, the re-dispatches of
a tiered wave included), ``gubernator_wave_slots_total`` (the width of
every launch's upload, padding included) and
``gubernator_wave_routed_rows_total`` (the rows those launches carry).
Prints one JSON line; exit 1 if the scrapes hold no wave.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import urllib.request

SIZE = "gubernator_dispatcher_wave_size"


def read(src: str) -> dict:
    """{series (name and labels): value} of one scrape."""
    if src.startswith(("http://", "https://")):
        with urllib.request.urlopen(src, timeout=60) as f:
            text = f.read().decode()
    else:
        with open(src) as f:
            text = f.read()
    out = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            name, _, val = line.rpartition(" ")
            out[name] = float(val)
    return out


def report(m0: dict, m1: dict, ladder: tuple) -> dict | None:
    def delta(prefix: str) -> float:
        return sum(v - m0.get(k, 0.0) for k, v in m1.items()
                   if k.startswith(prefix))

    waves = delta(SIZE + "_count")
    if waves <= 0:
        return None
    launches = delta("gubernator_wave_route_total")
    slots = delta("gubernator_wave_slots_total")
    routed = delta("gubernator_wave_routed_rows_total")
    # cumulative counts at the histogram's bounds → waves a rung
    at = {}
    for k, v in m1.items():
        if k.startswith(SIZE + "_bucket"):
            le = k.split('le="', 1)[1].split('"', 1)[0]
            at[float(le)] = v - m0.get(k, 0.0)
    shares, below = {}, 0.0
    for rung in ladder:
        if float(rung) not in at:
            shares[f"le_{rung}"] = None  # a program without this bound
            continue
        shares[f"le_{rung}"] = round(100 * (at[float(rung)] - below)
                                     / waves, 3)
        below = at[float(rung)]
    shares["over"] = round(100 * (waves - below) / waves, 3)
    out = {"waves": int(waves),
           "rows_per_wave": round(delta(SIZE + "_sum") / waves, 1),
           "wave_share_by_rung_pct": shares}
    if launches > 0:
        out.update(
            launches_per_wave=round(launches / waves, 4),
            slots_per_wave=round(slots / waves, 1),
            slots_per_launch=round(slots / launches, 1),
            routed_rows_per_wave=round(routed / waves, 1),
            pad_share_pct=round(100 * (1 - routed / slots), 3))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("scrapes", nargs="+", help="two files or URLs, or "
                    "one URL with --wait")
    ap.add_argument("--wait", type=float, default=0.0)
    ap.add_argument("--ladder", default="1024,8192,16384,32768")
    args = ap.parse_args(argv)
    if len(args.scrapes) == 1 and args.wait > 0:
        m0 = read(args.scrapes[0])
        time.sleep(args.wait)
        m1 = read(args.scrapes[0])
    elif len(args.scrapes) == 2:
        m0, m1 = (read(s) for s in args.scrapes)
    else:
        ap.error("two scrapes, or one URL with --wait")
    ladder = tuple(int(x) for x in args.ladder.split(",") if x.strip())
    rep = report(m0, m1, ladder)
    print(json.dumps(rep))
    return 0 if rep else 1


if __name__ == "__main__":
    sys.exit(main())
