"""Chip smoke test: drive the serving path once on a TPU, through the
entry points a user calls, and check what comes out.

  python chip_smoke.py [--seed N]     # one chip: served + catalog phases
  python chip_smoke.py --chips 4      # sharded catalog vs dense, 4 chips

Phases (one chip):

* served  — an MRES catalog whose ``qwen2-1.5b`` entry runs at its
  published widths (random weights from ``--seed``) next to reduced
  runners, the task analyzer loaded or trained as ``launch/serve.py``
  does, and ~16 requests across the preference profiles submitted
  twice through ``ServingEngine.submit`` (cold, then warm).  Every
  request must be admitted with in-vocabulary tokens, one fused
  analyze->route dispatch must serve each batch, and the full-width
  runner's first greedy token must match ``argmax`` of
  ``forward_full`` at the last prompt position.
* catalog — a 100k-entry synthetic catalog routed through
  ``OptiRoute.route_all`` at B in {1, 8, 64} with the XLA kNN and with
  the Pallas ``router_topk`` kernel: fp32 decisions must agree with
  each other and with the staged numpy reference (ties allowed), int8
  must meet the quantization-aware recall bar, and the warm pass must
  compile nothing.

With ``--chips 4`` only the catalog-sharded ``route_step`` over a
4-device mesh runs, against the dense single-device program on the
same catalog: candidates must be bit-identical.

Times are host-clock wall times around calls whose results are on
the host (the routing and generate paths copy their outputs back), with
the compiling first pass reported apart from the warm pass.  The run
fails (non-zero exit, no result line) when JAX sees no TPU; the last
line of a passing run is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import numpy as np  # noqa: E402

FULL_ARCH = "qwen2-1.5b"
# every entry gets a runner; the dense full-width model shares the
# catalog with an SSM, a hybrid and a second dense family
SERVED_ARCHS = (FULL_ARCH, "mamba2-1.3b", "hymba-1.5b", "h2o-danube-3-4b")
SCORE_TOL = 1e-4          # tie tolerance on blended scores (fp32)
LOGIT_TOL = 0.05          # bf16 near-tie tolerance on reference logits
RECALL_BAR = 0.99         # int8 quantization-aware recall@k
CATALOG_N = 100_000       # synthetic entries in the catalog phases


def _device() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _log(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def _require(ok, detail=None) -> None:
    """A failed check ends the run (stays on under ``python -O``)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {detail!r}")


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


# ----------------------------------------------------------------------
# served: catalog with a full-width runner behind the router
# ----------------------------------------------------------------------

def served_phase(full_cfg, analyzer, *, seed: int, n_requests: int = 16,
                 max_new: int = 8, archs=SERVED_ARCHS) -> dict:
    """Serve ``n_requests`` twice through ``ServingEngine.submit``;
    ``full_cfg`` is the config of the ``archs[0]`` entry's runner."""
    import jax.numpy as jnp

    from repro.configs import get_smoke
    from repro.core.mres import MRES
    from repro.core.orchestrator import OptiRoute
    from repro.core.preferences import PROFILES
    from repro.data.workload import make_workload
    from repro.kernels import ops as K
    from repro.models import model as M
    from repro.serving.catalog import build_entry
    from repro.serving.engine import Request, ServingEngine
    from repro.serving.runner import ModelRunner

    full_name = archs[0]
    full, t_init = _timed(lambda: ModelRunner(full_cfg, seed=seed))
    jax.block_until_ready(full.params)
    mres = MRES()
    for i, name in enumerate(archs):
        runner = full if i == 0 else ModelRunner(get_smoke(name),
                                                  seed=seed + i)
        mres.register(build_entry(name, runner=runner))
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(
        full.params))
    _log("served", step="catalog", full_runner=full_name,
         full_params=n_params, d_model=full_cfg.d_model,
         n_layers=full_cfg.n_layers, vocab=full_cfg.vocab_size,
         init_wall_s=t_init, entries=list(archs))

    router = OptiRoute(mres, analyzer)
    engine = ServingEngine(router)
    profiles = list(PROFILES)
    reqs = [Request(text=r.text, prefs=profiles[i % len(profiles)], id=i,
                    max_new=max_new)
            for i, r in enumerate(make_workload(n_requests, seed=seed))]

    events = []
    K.reset_route_step_stats()
    K.set_recompile_hook(events.append)
    try:
        cold, t_cold = _timed(lambda: engine.submit(reqs))
        warm, t_warm = _timed(lambda: engine.submit(reqs))
    finally:
        K.set_recompile_hook(None)
    stats = K.route_step_stats()

    for resps in (cold, warm):
        for r in resps:
            _require(r.admission == "admitted" and not r.cache_hit, (
                r.request.id, r.admission, r.error))
            vocab = mres.entry(r.model).runner.cfg.vocab_size
            toks = np.asarray(r.tokens)
            _require(toks.shape == (max_new,), (r.request.id, toks.shape))
            _require(0 <= toks.min() and toks.max() < vocab, (
                r.request.id, r.model, toks))
    _require([r.model for r in cold] == [r.model for r in warm])
    _require(all(np.array_equal(a.tokens, b.tokens)
                 for a, b in zip(cold, warm)), "warm pass changed tokens")
    # one fused analyze->route dispatch per submitted batch, no staged
    # analyze or staged routing underneath
    _require([e["path"] for e in events] == ["fused", "fused"], events)
    _require(stats["route_step_dispatches"] == 2, stats)
    _require(stats["analyze_step_dispatches"] == 2, stats)
    _require(stats["topk_dispatches"] == 0, stats)
    on_full = [r for r in warm if r.model == full_name]
    _require(on_full, f"no request routed to {full_name}")

    # reference: greedy first token == argmax of the full forward at
    # the last prompt position (ties within bf16 noise allowed)
    toks = engine._tokens([r.request.text for r in on_full],
                          full_cfg.vocab_size)
    fwd = jax.jit(lambda p, t: M.forward_full(
        p, full_cfg, {"tokens": t})[0][:, -1, :full_cfg.vocab_size])
    logits = np.asarray(fwd(full.params, jnp.asarray(toks)), np.float32)
    first = np.array([int(r.tokens[0]) for r in on_full])
    gap = logits.max(axis=1) - logits[np.arange(len(first)), first]
    exact = int((logits.argmax(axis=1) == first).sum())
    _require(np.isfinite(logits).all())
    _require((gap <= LOGIT_TOL).all(), (gap, first, logits.argmax(axis=1)))

    by_model = {}
    for r in warm:
        by_model[r.model] = by_model.get(r.model, 0) + 1
    out = {"requests": len(warm), "by_model": by_model,
           "served_full_width": len(on_full),
           "fused_dispatches": stats["route_step_dispatches"],
           "submit_cold_wall_s": t_cold, "submit_warm_wall_s": t_warm,
           "ref_first_token_exact": exact,
           "ref_first_token_max_gap": float(gap.max())}
    _log("served", **out)
    return out


# ----------------------------------------------------------------------
# catalog: 100k entries, XLA kNN vs the Pallas kernel vs staged numpy
# ----------------------------------------------------------------------

def _decisions_agree(a, b) -> bool:
    """Same fallback rung, and the same model or a tie in score."""
    return (a.fallback_kind == b.fallback_kind
            and (a.model == b.model or abs(a.score - b.score) <= SCORE_TOL)
            and abs(a.similarity - b.similarity) <= SCORE_TOL)


def _eps_recall(test, ref, embn, col, tol) -> float:
    """Quantization-aware recall@k of ``test`` against ``ref``
    decisions (primary-stage rows): a candidate whose exact cosine is
    within ``tol`` of the reference's k-th best counts as a hit — the
    metric of ``tests/test_mega_catalog.py``."""
    num = den = 0
    for t, r in zip(test, ref):
        if t.used_fallback or r.used_fallback:
            continue
        rrow = [col[n] for n, _ in r.candidates]
        trow = [col[n] for n, _ in t.candidates]
        qn = r.task_vector / (np.linalg.norm(r.task_vector) + 1e-9)
        ckth = float((embn[rrow] @ qn).min())
        den += len(rrow)
        num += min(len(rrow), int(((embn[trow] @ qn) >= ckth - tol).sum()))
    return num / max(den, 1)


def catalog_phase(analyzer, *, n: int, seed: int,
                  batches=(1, 8, 64)) -> dict:
    from benchmarks.router_scale import _mega_catalog
    from repro.core.orchestrator import OptiRoute
    from repro.core.preferences import PROFILES
    from repro.core.routing import RoutingEngine
    from repro.data.workload import make_workload
    from repro.kernels import ops as K

    mres, t_build = _timed(lambda: _mega_catalog(n, seed=seed))
    emb = mres.embeddings()
    names = mres.snapshot()[1]
    col = {m: j for j, m in enumerate(names)}
    embn = emb / (np.linalg.norm(emb, axis=1, keepdims=True) + 1e-9)
    m_dim = emb.shape[1]
    tol8 = float(np.sqrt(m_dim) / 127.0 + m_dim / (2.0 * 127.0 ** 2))
    profiles = list(PROFILES)
    wl = make_workload(max(batches), seed=seed + 1)
    texts = [r.text for r in wl]
    prefs = [profiles[i % len(profiles)] for i in range(len(wl))]
    _log("catalog", step="build", entries=n, build_wall_s=t_build)

    variants = {}
    for quant in (False, True):
        for kernel in (False, True):
            r = OptiRoute(mres, analyzer, use_kernel=kernel)
            r.engine.quantize = quant
            variants[("int8" if quant else "fp32",
                      "pallas" if kernel else "xla")] = r

    routed = {}
    for key, router in variants.items():
        cold_s = {}
        for b in batches:
            _, cold_s[b] = _timed(lambda: router.route_all(texts[:b],
                                                           prefs[:b]))
        before = K.route_step_stats()
        warm_s = {}
        for b in batches:
            routed[key, b], warm_s[b] = _timed(
                lambda: router.route_all(texts[:b], prefs[:b]))
        after = K.route_step_stats()
        compiles = (after["route_step_compiles"]
                    - before["route_step_compiles"])
        dispatches = (after["route_step_dispatches"]
                      - before["route_step_dispatches"])
        _require(compiles == 0, (key, before, after))
        _require(dispatches == len(batches), (key, before, after))
        _log("catalog", variant="/".join(key), batches=list(batches),
             cold_wall_s=cold_s, warm_wall_s=warm_s,
             warm_compiles=compiles, warm_dispatches=dispatches)

    oracle = RoutingEngine(mres, knn_k=variants["fp32", "xla"].engine.knn_k)
    out = {"entries": n}
    for b in batches:
        xla = [q.decision for q in routed[("fp32", "xla"), b]]
        pal = [q.decision for q in routed[("fp32", "pallas"), b]]
        # the staged reference routes each path's own analyzer output
        sig_x = [q.sig for q in routed[("fp32", "xla"), b]]
        sig_p = [q.sig for q in routed[("fp32", "pallas"), b]]
        _require([(s.task_type, s.domain) for s in sig_x]
                 == [(s.task_type, s.domain) for s in sig_p])
        agree_xp = sum(map(_decisions_agree, xla, pal))
        agree_xs = sum(map(_decisions_agree, xla,
                           oracle.route_many_staged(prefs[:b], sig_x)))
        agree_ps = sum(map(_decisions_agree, pal,
                           oracle.route_many_staged(prefs[:b], sig_p)))
        same_xp = sum(a.model == p.model for a, p in zip(xla, pal))
        rec = {v: _eps_recall([q.decision for q in routed[("int8", v), b]],
                              xla, embn, col, tol8)
               for v in ("xla", "pallas")}
        q8_same = sum(a.model == p.model for a, p in zip(
            [q.decision for q in routed[("int8", "xla"), b]],
            [q.decision for q in routed[("int8", "pallas"), b]]))
        row = {"batch": b, "fp32_xla_vs_pallas_agree": agree_xp,
               "fp32_xla_vs_pallas_same_model": same_xp,
               "fp32_xla_vs_staged_agree": agree_xs,
               "fp32_pallas_vs_staged_agree": agree_ps,
               "int8_recall_xla": rec["xla"],
               "int8_recall_pallas": rec["pallas"],
               "int8_xla_vs_pallas_same_model": q8_same}
        _log("catalog", **row)
        _require(agree_xp == agree_xs == agree_ps == b, row)
        _require(min(rec.values()) >= RECALL_BAR, row)
        out[b] = row
    return out


# ----------------------------------------------------------------------
# --chips 4: catalog-sharded route_step vs the dense program
# ----------------------------------------------------------------------

def sharded_phase(*, n: int, seed: int, n_devices: int,
                  batches=(1, 8, 64)) -> dict:
    from benchmarks.router_scale import _mega_catalog, _random_queries
    from repro.core.routing import RoutingEngine
    from repro.kernels import ops as K
    from repro.launch.mesh import make_routing_mesh

    mesh = make_routing_mesh(n_devices)
    devs = {d.id for d in mesh.devices.flat}
    _require(len(devs) == n_devices, mesh)
    mres, t_build = _timed(lambda: _mega_catalog(n, seed=seed))
    dense = RoutingEngine(mres, knn_k=8)
    shard = RoutingEngine(mres, knn_k=8, mesh=mesh)
    queries = {b: _random_queries(b, seed=seed + b) for b in batches}

    cold, warm = {}, {}
    for name, eng in (("dense", dense), ("sharded", shard)):
        for b in batches:
            _, cold[name, b] = _timed(
                lambda: eng.route_many_batch(*queries[b]))
    before = K.route_step_stats()
    results = {}
    for name, eng in (("dense", dense), ("sharded", shard)):
        for b in batches:
            results[name, b], warm[name, b] = _timed(
                lambda: eng.route_many_batch(*queries[b]))
    after = K.route_step_stats()
    compiles = after["route_step_compiles"] - before["route_step_compiles"]
    _require(compiles == 0, (before, after))

    # the packed catalog really is spread: one row block per device
    np_pad = K.n_bucket_sharded(n, n_devices)
    spread = [a for a in jax.live_arrays()
              if a.shape[:1] == (np_pad,) and len(a.sharding.device_set)
              == n_devices]
    _require(spread, "no catalog array is sharded across the mesh")
    for a in spread:
        shards = a.addressable_shards
        _require(len({s.device.id for s in shards}) == n_devices)
        _require(all(s.data.shape[0] == np_pad // n_devices for s in shards))

    out = {"entries": n, "devices": sorted(devs), "build_wall_s": t_build}
    for b in batches:
        d, s = results["dense", b], results["sharded", b]
        same = (np.array_equal(d.cand_idx, s.cand_idx)
                and d.models() == s.models())
        row = {"batch": b, "bit_identical": bool(same),
               "dense_cold_wall_s": cold["dense", b],
               "sharded_cold_wall_s": cold["sharded", b],
               "dense_warm_wall_s": warm["dense", b],
               "sharded_warm_wall_s": warm["sharded", b]}
        _log("sharded", **row)
        _require(same, row)
        out[b] = row
    _log("sharded", warm_compiles=compiles, sharded_arrays=len(spread))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the catalog-sharded phase")
    args = ap.parse_args(argv)

    dev = _device()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU visible (platform={dev['platform']})",
              file=sys.stderr)
        return 2
    if dev["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {dev['count']}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    _log("start", device=dev, compile_cache=enable_compile_cache(),
         seed=args.seed)
    if args.chips == 4:
        sharded_phase(n=CATALOG_N, seed=args.seed, n_devices=4)
    else:
        from repro.configs import get_config
        from repro.launch.serve import load_analyzer
        analyzer, t_an = _timed(load_analyzer)
        _log("analyzer", wall_s=t_an)
        served_phase(get_config(FULL_ARCH), analyzer, seed=args.seed)
        catalog_phase(analyzer, n=CATALOG_N, seed=args.seed)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
