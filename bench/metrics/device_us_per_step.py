"""Device-busy time per event-loop iteration, on one chip: busy seconds of
the traced window over the ``while`` iterations the window ran (the
drivers' layer, ``core/engine.py``)."""


def read(r):
    if r.iterations <= 0 or r.busy_s <= 0:
        return None
    return 1e6 * r.busy_s / r.iterations
