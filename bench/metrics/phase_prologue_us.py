"""Device time of the ops under the ``phase_prologue`` scope
(``core/step.py``: outage edges, transfer settling, instrument ``pre``
hooks, release of drained VMs), summed over chips, per scenario answered."""


def read(r):
    t = r.scope_s("phase_prologue")
    if t <= 0 or r.n_scenarios <= 0:
        return None
    return 1e6 * t / r.n_scenarios
