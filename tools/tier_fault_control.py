"""A control for the tiered deployments (cells ``r1-churn-100m`` and
``r1-drift-100m``): ONE fault
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
(On the native store's lane, where ONE C++ pass applies a wave, "request"
reads "key of a wave that the store holds": ``inject``.)
``pass-forget`` / ``pass-fork`` put the same two faults INSIDE a migration
pass (``tiering.py › TierController.migrate``; ISSUE 46, cell
``r1-drift-100m``, whose work is migration) and leave the cold lane
alone: every ``every``-th row a pass promotes is dropped from the host
store WITHOUT being placed on the device (forgotten: in neither tier),
or is placed as it was before its last hit (forked: the hit that the
cold lane had just applied to it, in the same wave, is lost).
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
    """Patch the cold lane's transition on BOTH its lanes; returns the
    live counts.  The Python loop calls ``tiering._host_apply`` once a
    cold row: the fault is put there, a request at a time.  The C++ pass
    (``_NativeColdStore._apply_batch``: the native store's lane, the
    cell's since ISSUE 42) applies a whole wave: the fault is put at the
    store round it, a held KEY of a wave at a time — forgotten before
    the pass, or given its old row back after it."""
    from gubernator_tpu import tiering

    if fault not in ("forget", "fork", "pass-forget", "pass-fork"):
        raise SystemExit(f"no fault {fault!r}: forget, fork, pass-forget "
                         "or pass-fork")
    done = {"held": 0, "faults": 0, "live": 0}
    if fault.startswith("pass-"):
        return inject_in_pass(fault[5:], every, done)
    expire_at = tiering.ROW_COLS.index("expire_at")
    apply = tiering._host_apply

    def pick(row, clock) -> bool:
        """Whether this held row is the ``every``-th: counted if so."""
        done["held"] += 1
        if done["held"] % every:
            return False
        done["faults"] += 1
        done["live"] += row[expire_at] > clock  # at its clock
        return True

    def faulty(row, *request):
        if row is None or not pick(row, request[-1]):
            return apply(row, *request)
        if fault == "forget":
            return apply(None, *request)
        return (*apply(row, *request)[:4], row)  # the write is lost

    apply_batch = tiering._NativeColdStore._apply_batch

    def faulty_batch(store, khash, idxs, req_cols, now_ms, cols):
        # the wave's held keys, each at the row that comes first
        rows = idxs[store.contains_batch(khash[idxs])]
        first = {}
        for i in rows.tolist():
            first.setdefault(int(khash[i]), i)
        picked = []
        for kh, i in first.items():
            row = store.get(kh)
            if pick(row, int(req_cols[-1][i]) or now_ms):
                picked.append((kh, row))
                if fault == "forget":
                    store.pop(kh)
        out = apply_batch(store, khash, idxs, req_cols, now_ms, cols)
        if fault == "fork":
            for kh, row in picked:
                store.put(kh, row)  # the wave's writes are lost
        return out

    tiering._host_apply = faulty
    tiering._NativeColdStore._apply_batch = faulty_batch
    return done


def inject_in_pass(fault: str, every: int, done: dict) -> dict:
    """Patch the migration pass: the image a pass works on
    (``engine.tier_image``) is handed over with a ``place`` that faults
    every ``every``-th row it is given.  A promotee was served cold in
    this very wave, so its row is live at the pass: every fault can
    show."""
    import numpy as np

    from gubernator_tpu import tiering

    remaining = tiering.ROW_COLS.index("remaining")
    limit = tiering.ROW_COLS.index("limit")
    migrate = tiering.TierController._migrate

    def faulty_migrate(tc, engine, khs, ranks):
        real = engine.tier_image

        def image(keys):
            img = real(keys)
            place = img.place

            def faulty_place(sel, rows):
                sel = np.asarray(sel)
                rows = np.array(rows, copy=True)
                picked = np.zeros(len(sel), bool)
                for i in range(len(sel)):
                    done["held"] += 1
                    if done["held"] % every == 0:
                        picked[i] = True
                done["faults"] += int(picked.sum())
                done["live"] += int(picked.sum())
                if fault == "fork":  # its last hit is lost
                    lost = picked & (rows[:, remaining] < rows[:, limit])
                    rows[lost, remaining] += 1
                    return place(sel, rows)
                out = np.ones(len(sel), bool)  # "placed": dropped cold
                out[~picked] = place(sel[~picked], rows[~picked])
                return out

            img.place = faulty_place
            return img

        engine.tier_image = image
        try:
            return migrate(tc, engine, khs, ranks)
        finally:
            del engine.tier_image  # the class's own again

    tiering.TierController._migrate = faulty_migrate
    return done


def main() -> int:
    fault, every = sys.argv[1], int(sys.argv[2])
    if sys.argv[3] != "--":
        raise SystemExit(__doc__)
    sys.argv = [os.path.join(REPO, "benchmark", "run.py"), *sys.argv[4:]]
    done = inject(fault, every)
    from benchmark import run

    rc = run.main()
    what = ("rows a migration pass placed" if fault.startswith("pass-")
            else "requests that found their row cold")
    print(f"tier fault {fault!r}: {done['faults']} of {done['held']} "
          f"{what}, {done['live']} of them a row still live",
          file=sys.stderr, flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
