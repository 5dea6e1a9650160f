"""Sharding rules: validity, divisibility fallbacks, memory model."""
import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.models import TRAIN_4K, DECODE_32K, build_model
from repro.dist import param_pspec_tree, input_pspec_tree


def _fake_mesh(shape, axes):
    """Abstract mesh for spec derivation only (no real devices needed)."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(shape, axes)


MESH = _fake_mesh((16, 16), ("data", "model"))


def _check_specs(shapes, specs, mesh):
    for leaf, spec in zip(
        jax.tree.leaves(shapes),
        jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)),
    ):
        assert len(spec) <= len(leaf.shape)
        used = set()
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * 10):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            for a in axes:
                assert a not in used, f"axis {a} reused in {spec}"
                used.add(a)
            total = int(np.prod([mesh.shape[a] for a in axes]))
            assert dim % total == 0, (
                f"dim {dim} not divisible by {axes} ({total}) in {spec}")


def test_param_specs_all_archs():
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        model = build_model(cfg)
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        specs = param_pspec_tree(shapes, MESH)
        _check_specs(shapes, specs, MESH)


def test_input_specs_all_archs():
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        model = build_model(cfg)
        for cell in (TRAIN_4K, DECODE_32K):
            specs = model.input_specs(cell)
            pspecs = input_pspec_tree(specs, MESH)
            _check_specs(specs, pspecs, MESH)


def test_whisper_vocab_fallback():
    """51866 is not 16-divisible: embed must not shard V over model."""
    cfg = get_config("whisper-large-v3")
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = param_pspec_tree(shapes, MESH)
    assert specs["embed"][0] is None


def test_moe_expert_sharding():
    cfg = get_config("qwen3-moe-235b-a22b")
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = param_pspec_tree(shapes, MESH)
    wg = specs["periods"]["sub0"]["mlp"]["w_gate"]
    assert wg == P(None, "model", None, "data")  # (layers, E, D, F)
