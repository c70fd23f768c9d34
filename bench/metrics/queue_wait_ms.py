"""Mean host ms from a request's release into the scheduler's queue to
its admission, over the requests admitted in the run (the scheduler's own
host stamps, ``ServingRequest.host_released_s`` and ``host_admitted_s``;
nothing where the program has no such stamps)."""
from bench import window as Wn


def read(ctx):
    waits = [r.host_admitted_s - r.host_released_s
             for r in Wn.admitted(ctx)
             if getattr(r, "host_admitted_s", None) is not None
             and getattr(r, "host_released_s", None) is not None]
    return sum(waits) * 1e3 / len(waits) if waits else None
