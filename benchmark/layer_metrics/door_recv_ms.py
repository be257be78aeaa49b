"""Mean of phase `door.recv`: a call's task has started on a pool
thread → the servicer's first line — grpcio waiting for the request
message, which the one `_serve` loop hands over under the GIL, and its
prelude (`daemon.py › DoorPool`, `_V1Servicer`).
`gubernator_phase_duration{phase="door.recv"}` between the window's
scrapes; 1 call in 8.  With `door_wait_ms` it is the part of
`frontdoor_ms` between the call's announcement and the program's first
line.  A program without the phase reads nothing."""
from benchmark.harness import progspans


def read(ctx):
    return progspans.ms_per_sample(ctx, "door.recv")
