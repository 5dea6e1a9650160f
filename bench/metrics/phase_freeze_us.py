"""Device time of the ops under the ``phase_freeze`` scope
(``core/step.py``: the batch step's per-row freeze of finished rows),
summed over chips, per scenario answered."""


def read(r):
    t = r.scope_s("phase_freeze")
    if t <= 0 or r.n_scenarios <= 0:
        return None
    return 1e6 * t / r.n_scenarios
