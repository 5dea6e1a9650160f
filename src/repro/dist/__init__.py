"""repro.dist — sharding rule trees + activation sharding (DESIGN.md §6).

Two halves:

* ``repro.dist.sharding`` — *static* layout: PartitionSpec trees derived
  from path/shape rule tables with divisibility fallbacks
  (``param_pspec_tree`` / ``input_pspec_tree`` / ``rules_for_mesh``), and
  ``named`` to bind them to a concrete mesh.
* ``repro.dist.act_sharding`` — *dynamic* layout: the
  ``activation_shardings`` context models consult while tracing
  (``shard_act`` constraints, ``current_state`` for schedule selection).

Every shard_map call site in the tree calls ``jax.shard_map`` with
``check_vma=False`` directly.
"""
from repro.dist import act_sharding, sharding
from repro.dist.act_sharding import activation_shardings, current_state, shard_act
from repro.dist.sharding import (
    Rules,
    input_pspec_tree,
    named,
    param_pspec_tree,
    rules_for_mesh,
)

__all__ = [
    "Rules",
    "act_sharding",
    "activation_shardings",
    "current_state",
    "input_pspec_tree",
    "named",
    "param_pspec_tree",
    "rules_for_mesh",
    "shard_act",
    "sharding",
]
