"""Device time of the ops under the ``loop_cond`` scope
(``core/engine.py``: the event loop's condition, and the batch step's
live mask), summed over chips, per scenario answered."""


def read(r):
    t = r.scope_s("loop_cond")
    if t <= 0 or r.n_scenarios <= 0:
        return None
    return 1e6 * t / r.n_scenarios
