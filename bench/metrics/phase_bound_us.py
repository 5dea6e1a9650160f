"""Device time of the ops under the ``phase_bound`` scope
(``core/step.py``: the policy sweep, the candidate event times, instrument
``bound`` hooks), summed over chips, per scenario answered."""


def read(r):
    t = r.scope_s("phase_bound")
    if t <= 0 or r.n_scenarios <= 0:
        return None
    return 1e6 * t / r.n_scenarios
