"""Device time of the ops under the ``phase_consolidate`` scope
(``core/step.py``: the power-aware consolidation pass at scheduling ticks),
summed over chips, per scenario answered."""


def read(r):
    t = r.scope_s("phase_consolidate")
    if t <= 0 or r.n_scenarios <= 0:
        return None
    return 1e6 * t / r.n_scenarios
