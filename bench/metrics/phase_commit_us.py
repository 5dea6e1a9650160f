"""Device time of the ops under the ``phase_commit`` scope
(``core/step.py``: the state update and instrument ``post`` hooks), summed
over chips, per scenario answered."""


def read(r):
    t = r.scope_s("phase_commit")
    if t <= 0 or r.n_scenarios <= 0:
        return None
    return 1e6 * t / r.n_scenarios
