"""Device time of the consolidation pass's sequential placement loops
(scope ``consolidate_place``, ``core/consolidate.py``), summed over chips,
per scenario answered.  The loops run inside the batch step's vmapped
pass, where the scope's path component reads ``vmap(consolidate_place)``;
both spellings count, each op once."""
from bench.harness import trace as tr

SCOPES = ("consolidate_place", "vmap(consolidate_place)")


def read(r):
    t = sum(tr.covered(tr.union(iv for s in SCOPES
                                for iv in r.trace.in_scope(d, s)), r.lo, r.hi)
            for d in r.devices)
    if t <= 0 or r.n_scenarios <= 0:
        return None
    return 1e6 * t / r.n_scenarios
