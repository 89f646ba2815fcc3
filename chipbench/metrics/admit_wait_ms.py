"""Serving loop (``serving/admission.py``): mean time a request waited in the
admission queue before it was seated, over every ``admission.launch`` event's
``waits_s`` in the window.  The client's own hold before submission is not
in it.  Moves ``p50_ms``."""


def read(ctx):
    waits = [w for e in ctx.events.get("admission.launch", []) for w in e.get("waits_s", [])]
    return 1e3 * sum(waits) / len(waits) if waits else None
