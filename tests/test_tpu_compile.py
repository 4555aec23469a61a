"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The TPU compiler refuses what interpret mode accepts — an in-kernel
``top_k``, misaligned tiles, programs that overflow the device — so
the main path's kernels and steps are compiled here at real widths,
one v5e chip each.  The topology is described inside a fixture: only
the worker that runs this file loads the TPU library.  Nothing runs;
these say nothing about results or times.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.preferences import DOMAINS, METRICS, TASK_TYPES
from repro.kernels.router_topk import Q8_BLK_Q

N_CATALOG = 100_096          # ops.n_bucket(100_000)
N_TT, N_DM = len(TASK_TYPES) + 1, len(DOMAINS) + 1
M = len(METRICS)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off
    (entries written for a described chip cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(sharding, tree):
    """ShapeDtypeStructs of ``tree``'s leaves placed on ``sharding``."""
    return jax.tree_util.tree_map(
        lambda x: _sds(sharding, x.shape, x.dtype), tree)


@pytest.mark.parametrize("quant,blk_q,blk_n", [
    (False, 8, 512), (False, 8, 128), (True, Q8_BLK_Q, 512),
    (True, Q8_BLK_Q, 128)])
def test_router_topk_kernel_compiles(one_chip, quant, blk_q, blk_n):
    from repro.kernels.router_topk import (router_topk_pallas,
                                           router_topk_q8_pallas)
    Q, N, D, k = 64, 4096, 128, 8
    s = lambda *a: _sds(one_chip, *a)  # noqa: E731
    mask, bias = s((Q, N), jnp.float32), s((1, N), jnp.float32)
    if quant:
        fn = lambda q, e, qs, es, m, b: router_topk_q8_pallas(  # noqa: E731
            q, e, qs, es, m, b, k, blk_q=blk_q, blk_n=blk_n,
            interpret=False)
        args = (s((Q, D), jnp.int8), s((N, D), jnp.int8),
                s((Q, 1), jnp.float32), s((1, N), jnp.float32), mask, bias)
    else:
        fn = lambda q, e, m, b: router_topk_pallas(  # noqa: E731
            q, e, m, b, k, blk_q=blk_q, blk_n=blk_n, interpret=False)
        args = (s((Q, D), jnp.float32), s((N, D), jnp.float32), mask, bias)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _route_operands(sharding, quant):
    s = lambda *a: _sds(sharding, *a)  # noqa: E731
    rows = N_TT * N_DM + N_TT + 2
    return dict(
        e2=s((N_CATALOG, 2 * M), jnp.int8 if quant else jnp.float32),
        e2s=s((N_CATALOG, 2) if quant else (1, 2), jnp.float32),
        masks_table=s((rows, N_CATALOG), jnp.bool_),
        counts_table=s((rows,), jnp.int32),
        theta=s((1, 1), jnp.float32), ainv_flat=s((1, 1), jnp.float32),
        lpen=s((1,), jnp.float32))


@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("quant", [False, True])
def test_route_step_compiles(one_chip, use_pallas, quant):
    from repro.kernels.route_step import route_step_jit
    qp = 64
    s = lambda *a: _sds(one_chip, *a)  # noqa: E731
    o = _route_operands(one_chip, quant)
    compiled = route_step_jit.lower(
        o["e2"], o["e2s"], o["masks_table"], o["counts_table"],
        s((qp, M), jnp.float32), s((qp, M), jnp.float32),
        s((qp,), jnp.int32), s((qp,), jnp.int32), s((1, 1), jnp.float32),
        o["theta"], o["ainv_flat"], o["lpen"], s((3,), jnp.float32),
        k=8, r=8, n_tt=N_TT, n_dm=N_DM, has_fb=False, has_ad=False,
        has_load=False, use_pallas=use_pallas, blk_q=8, blk_n=128,
        interpret=False, quant=quant).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == use_pallas


@pytest.mark.parametrize("quant", [False, True])
def test_analyze_route_step_compiles_with_kernel(one_chip, quant):
    from repro.core.analyzer import AnalyzerConfig, init_analyzer
    from repro.kernels.analyze_step import analyze_route_step_jit
    cfg = AnalyzerConfig()
    qp = 64
    s = lambda *a: _sds(one_chip, *a)  # noqa: E731
    params = _on(one_chip, jax.eval_shape(
        lambda: init_analyzer(jax.random.PRNGKey(0), cfg)))
    o = _route_operands(one_chip, quant)
    compiled = analyze_route_step_jit.lower(
        params, s((qp, cfg.max_len), jnp.int32), s((qp, M), jnp.float32),
        s((1,), jnp.float32), s((1, 1), jnp.float32), o["e2"], o["e2s"],
        o["masks_table"], o["counts_table"], o["theta"], o["ainv_flat"],
        o["lpen"], s((3,), jnp.float32), cfg=cfg, acc_col=0,
        use_complexity=True, fb_buckets=4, k=8, r=8, n_tt=N_TT,
        n_dm=N_DM, has_fb=False, has_ad=False, has_load=False,
        use_pallas=True, blk_q=8, blk_n=128, interpret=False,
        quant=quant).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _decision_program(sharding, quant):
    """decide-1m-open's program compiled for one described v5e: Qp 8,
    a 1,000,064-column catalog, no Pallas; fp32 or int8 catalog."""
    from repro.core.analyzer import AnalyzerConfig, init_analyzer
    from repro.kernels.analyze_step import analyze_route_step_jit
    cfg = AnalyzerConfig()
    qp, n_cat = 8, 1_000_064
    s = lambda *a: _sds(sharding, *a)  # noqa: E731
    params = _on(sharding, jax.eval_shape(
        lambda: init_analyzer(jax.random.PRNGKey(0), cfg)))
    rows = N_TT * N_DM + N_TT + 2
    return analyze_route_step_jit.lower(
        params, s((qp, cfg.max_len), jnp.int32), s((qp, M), jnp.float32),
        s((1,), jnp.float32), s((1, 1), jnp.float32),
        s((n_cat, 2 * M), jnp.int8 if quant else jnp.float32),
        s((n_cat, 2) if quant else (1, 2), jnp.float32),
        s((rows, n_cat), jnp.bool_), s((rows,), jnp.int32),
        s((1, 1), jnp.float32), s((1, 1), jnp.float32),
        s((1,), jnp.float32), s((3,), jnp.float32), cfg=cfg, acc_col=0,
        use_complexity=True, fb_buckets=4, k=8, r=8, n_tt=N_TT,
        n_dm=N_DM, has_fb=False, has_ad=False, has_load=False,
        use_pallas=False, blk_q=8, blk_n=128, interpret=False,
        quant=quant).compile()


def _entry_bytes(compiled):
    """(entry computation's HLO text, XLA's bytes-accessed estimate)."""
    text = compiled.as_text()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    return text[text.index("ENTRY"):], cost["bytes accessed"]


def test_decision_program_select_stage_relays_out_nothing(one_chip):
    """decide-1m-open's program: no ``while`` in the entry computation,
    and at most 0.6 GB accessed by XLA's estimate.  A chunk-of-32
    top-k, a row gather of the mask table and gathers from halves of
    the catalog block read 1.64 GB, through two ``while`` copies of
    the scores, a gather of the whole mask table and a row-major copy
    of the catalog block."""
    entry, nbytes = _entry_bytes(_decision_program(one_chip, False))
    assert "while(" not in entry
    assert nbytes <= 0.6e9, nbytes


def test_decision_program_int8_catalog_reads_under_600mb(one_chip):
    """The same program over the int8 catalog block: no ``while`` and
    at most 0.6 GB accessed (1.90 GB with the chunk-of-32 top-k).  One
    row-major copy of the 16-MB int8 block remains, for the candidate
    gather: XLA:TPU makes it for a row or a column gather alike."""
    entry, nbytes = _entry_bytes(_decision_program(one_chip, True))
    assert "while(" not in entry
    assert nbytes <= 0.6e9, nbytes


def test_bandit_update_compiles(one_chip):
    from repro.kernels.bandit_update import bandit_update_pallas
    s = lambda *a: _sds(one_chip, *a)  # noqa: E731
    bu, bs, n, dp, p2 = 8, 8, 1024, 128, 128
    compiled = jax.jit(lambda *a: bandit_update_pallas(
        *a, blk_n=128, interpret=False)).lower(
        s((bu, n), jnp.float32), s((bu, p2), jnp.float32),
        s((bu, dp), jnp.float32), s((bs, dp), jnp.float32),
        s((bs, p2), jnp.float32), s((n, dp), jnp.float32),
        s((n, p2), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_qwen2_decode_step_compiles_at_full_width(one_chip):
    """The served full-width runner's decode step (B=8, 152-token
    cache) fits one 16 GB chip."""
    from repro.configs import get_config
    from repro.models import model as M
    from repro.training.steps import make_decode_step
    cfg = get_config("qwen2-1.5b")
    B, ctx = 8, 152
    params = _on(one_chip, jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg)))
    cache = _on(one_chip, jax.eval_shape(lambda: M.init_cache(cfg, B, ctx)))
    batch = {"token": _sds(one_chip, (B, 1), jnp.int32),
             "pos": _sds(one_chip, (B,), jnp.int32)}
    compiled = jax.jit(make_decode_step(cfg)).lower(
        params, cache, batch).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16e9, used
