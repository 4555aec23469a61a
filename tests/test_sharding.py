"""Sharding rules coherence on a small host-side mesh.

The full 256/512-chip lowering is proven by the dry-run sweep
(results/dryrun/*.json, EXPERIMENTS.md §Dry-run); these tests check the
rule layer itself: spec trees match param trees, divisibility handling,
and an actual pjit run on a tiny (1,1) mesh.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_NAMES, get_config, get_smoke
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.sharding import rules as R


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_param_specs_cover_tree(arch):
    cfg = get_config(arch)
    mesh = make_host_mesh()
    shapes = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    specs = R.param_specs(cfg, mesh, shapes)
    flat_s, tdef_s = jax.tree_util.tree_flatten(specs)
    flat_p, tdef_p = jax.tree_util.tree_flatten(shapes)
    assert tdef_s == tdef_p
    for spec, leaf in zip(flat_s, flat_p):
        assert isinstance(spec, P)
        assert len(spec) <= len(leaf.shape)


def test_divisibility_drives_sharding():
    from repro.launch import dryrun  # noqa: F401 — not imported here; use mesh math
    cfg = get_config("qwen2-1.5b")
    mesh = make_host_mesh()            # axes sizes 1 -> everything "shards"
    assert R.maybe(mesh, 10, "model") == "model"   # 10 % 1 == 0
    assert R.axis_size(mesh, ("data", "model")) == 1
    assert R.axis_size(mesh, None) == 1


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_model_dims_divisible_by_16(arch):
    """DESIGN.md §5 claim: all sharded dims divide the 16-way model axis."""
    cfg = get_config(arch)
    assert cfg.vocab_padded % 16 == 0
    assert cfg.d_model % 16 == 0
    if cfg.d_ff:
        assert cfg.d_ff % 16 == 0 or cfg.is_moe
    if cfg.has_attention:
        assert cfg.q_dim % 16 == 0
    if cfg.is_moe:
        assert cfg.n_experts % 16 == 0


def test_pjit_train_step_on_host_mesh():
    """Full pjit path (specs -> jit -> run) on the 1-device mesh."""
    from repro.training.optimizer import init_opt_state
    from repro.training.steps import make_train_step
    cfg = get_smoke("llama3.2-1b")
    mesh = make_host_mesh()
    rng = np.random.default_rng(0)
    with jax.set_mesh(mesh):
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        pspecs = R.param_specs(cfg, mesh, params)
        opt = init_opt_state(params)
        ospecs = {"mu": pspecs, "nu": pspecs, "step": P()}
        batch = {"tokens": jnp.asarray(rng.integers(0, 64, (2, 16)), jnp.int32),
                 "labels": jnp.asarray(rng.integers(0, 64, (2, 16)), jnp.int32)}
        bspecs = R.batch_spec(cfg, mesh, batch)
        step = jax.jit(make_train_step(cfg),
                       in_shardings=(pspecs, ospecs, bspecs))
        params2, opt2, metrics = step(params, opt, batch)
        assert np.isfinite(float(metrics["loss"]))


def test_cache_specs_long_context_shards_length():
    """batch=1 decode shards the cache length axis over data (DESIGN §5)."""
    cfg = get_config("mamba2-1.3b")
    mesh = make_host_mesh()
    cache = jax.eval_shape(lambda: M.init_cache(cfg, 1, 1024))
    specs = R.cache_specs(cfg, mesh, cache)
    assert "ssd" in specs and isinstance(specs["ssd"], P)
    cfg2 = get_config("h2o-danube-3-4b")
    cache2 = jax.eval_shape(lambda: M.init_cache(cfg2, 1, 4096))
    specs2 = R.cache_specs(cfg2, mesh, cache2)
    # KV cache present and spec'd per (k, v)
    assert set(specs2) >= {"k", "v"}


def test_maybe_counts_silent_replications():
    """Every ``maybe`` fallback to replication (non-dividing dim) bumps
    the audit counter the dry-run surfaces — divisible dims don't."""
    mesh = make_host_mesh()                      # both axes size 1
    base = R.silent_replication_count()
    assert R.maybe(mesh, 10, "model") == "model"
    assert R.silent_replication_count() == base  # clean shard: no bump
    mesh4 = jax.make_mesh((4,), ("data",))
    assert R.maybe(mesh4, 8, "data") == "data"
    assert R.silent_replication_count() == base
    assert R.maybe(mesh4, 6, "data") is None     # 6 % 4 != 0: replicate
    assert R.maybe(mesh4, 1, "data") is None
    assert R.silent_replication_count() == base + 2
    R.reset_silent_replication_count()
    assert R.silent_replication_count() == 0


def test_route_step_specs_cover_catalog_axis():
    """The mega-catalog routing specs shard every (.., N) operand over
    the catalog axis and replicate the per-query operands."""
    mesh = jax.make_mesh((4,), (R.CATALOG_AXIS,))
    specs = R.route_step_specs(mesh)
    assert specs["e2"] == P(R.CATALOG_AXIS, None)
    assert specs["masks_table"] == P(None, R.CATALOG_AXIS)
    assert specs["lpen"] == P(R.CATALOG_AXIS)
    assert specs["counts_table"] == P()
    assert specs["query"] == P()
    with pytest.raises(AssertionError):
        R.route_step_specs(make_host_mesh())     # no catalog axis
