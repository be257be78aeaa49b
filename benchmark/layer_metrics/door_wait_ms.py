"""Mean of phase `door.wait`: grpcio hands a call to the handler pool
(on its `_serve` thread, when the call is announced) → the call's task
starts on a pool thread — the pool's queue, the thread's wake-up,
winning the GIL (`daemon.py › DoorPool`, `_V1Servicer`).
`gubernator_phase_duration{phase="door.wait"}` between the window's
scrapes; 1 call in 8.  A program without the phase reads nothing."""
from benchmark.harness import progspans


def read(ctx):
    return progspans.ms_per_sample(ctx, "door.wait")
