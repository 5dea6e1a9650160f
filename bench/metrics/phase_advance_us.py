"""Device time of the ops under the ``phase_advance`` scope
(``core/step.py``: the advance sweep, jnp or Pallas), summed over chips,
per scenario answered.  Where XLA fuses the jnp sweep's completion
min-reduce into a fusion of another phase, that fusion carries the other
phase's name: in ``fig9_10.single`` it is ``phase_bound``'s, and this
metric finds nothing to read there."""


def read(r):
    t = r.scope_s("phase_advance")
    if t <= 0 or r.n_scenarios <= 0:
        return None
    return 1e6 * t / r.n_scenarios
