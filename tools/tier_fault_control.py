"""A control for the tiered deployment (cell ``r1-churn-100m``): ONE fault
of the cold tier's own guarantee — "a key's bucket is never forgotten or
forked, whichever tier holds the row" — put into the program, and the
benchmark's cell run on it.  The run must end NOT correct: that shows the
window rules and the replay catch the fault at the cell's load, where
``--control float32`` (the reference at a lower precision in the
program's place) breaks every row's ``reset_time`` and shows nothing of
the tier.

    python tools/tier_fault_control.py <fault> <every> -- \\
        --workload r1-churn-100m --seed N --seconds 51 --trace 0

``forget``: every ``every``-th request that finds its row in the cold
store is applied as if the store held none, so it opens a fresh bucket
in the row's place (what upstream's LRU does to an evicted key).
``fork``: every ``every``-th such request is answered but its write is
lost, so the key's next request is answered from the state before it.
The last line on standard error says how many rows were faulted and how
many of them were LIVE at the request's clock: forgetting a bucket that
has run out changes no answer (an expired row IS a missing one), so
only those can show.  Everything after ``--`` is ``benchmark/run.py``'s
own command line; the daemon runs in that process, so the patch below is
all it takes.  Not an option of the program: the fault lives here.
"""
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def inject(fault: str, every: int) -> dict:
    """Patch the cold lane's transition (``tiering._host_apply``, which
    ``resolve`` calls once a cold row); returns the live counts."""
    from gubernator_tpu import tiering

    if fault not in ("forget", "fork"):
        raise SystemExit(f"no fault {fault!r}: forget or fork")
    done = {"held": 0, "faults": 0, "live": 0}
    expire_at = tiering.ROW_COLS.index("expire_at")
    apply = tiering._host_apply

    def faulty(row, *request):
        if row is None:
            return apply(row, *request)
        done["held"] += 1
        if done["held"] % every:
            return apply(row, *request)
        done["faults"] += 1
        done["live"] += row[expire_at] > request[-1]  # at its clock
        if fault == "forget":
            return apply(None, *request)
        return (*apply(row, *request)[:4], row)  # the write is lost

    tiering._host_apply = faulty
    return done


def main() -> int:
    fault, every = sys.argv[1], int(sys.argv[2])
    if sys.argv[3] != "--":
        raise SystemExit(__doc__)
    sys.argv = [os.path.join(REPO, "benchmark", "run.py"), *sys.argv[4:]]
    done = inject(fault, every)
    from benchmark import run

    rc = run.main()
    print(f"tier fault {fault!r}: {done['faults']} of {done['held']} "
          f"requests that found their row cold, {done['live']} of them "
          "a row still live", file=sys.stderr, flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
