"""Build the system under test from a configuration file.

Everything the program receives is made here from the configuration
and ``--seed``: the catalog, the analyzer's weights, the backend's
weights.  A configuration that fixes ``catalog.seed`` serves one
registry whatever ``--seed`` is, as a deployment does; only its traffic
then changes with the seed.  The program is driven only through its
public entry points (``MRES``, ``OptiRoute``, ``ServingEngine``,
``AsyncServingEngine``, ``ModelRunner``); the reference views of the
same data stay with the benchmark.
"""
from __future__ import annotations

import pathlib
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import numpy as np

from benchlib import backend, inputs, reference, weights

N_TT = len(inputs.TASK_TYPES)
N_DM = len(inputs.DOMAINS)
RAW_KEYS = tuple(r for r, _, _ in reference.RAW_AXES)


@dataclass
class System:
    config: dict
    router: object                       # OptiRoute
    engine: object                       # ServingEngine
    names: List[str]                     # catalog rows, in order
    row_of: Dict[str, int]
    ref_catalog: reference.Catalog
    analyzer_params: Dict                # numpy, bench-made
    backend: Optional[dict] = None       # served model dims (config)
    backend_name: str = ""
    backend_weights: Optional[Dict] = None   # bench-made
    runner: object = None                # ModelRunner of the backend
    on_chip: set = field(default_factory=set)


def _catalog_rows(cfg: dict, seed: int):
    """The catalog, drawn from ``catalog.seed`` where the configuration
    fixes one, else from ``seed``: (names, raw metrics (n, 8) in
    RAW_KEYS order, task-type members (n, n_tt), domain members (n,
    n_dm), generalist (n,), backend of each row or None)."""
    cat = cfg["catalog"]
    if cat["generator"] == "mega":
        raw01, tt, dm, gen = inputs.mega_catalog_arrays(
            cat["entries"], seed=cat.get("seed", seed),
            clusters=cat["clusters"], noise=cat["noise"],
            generalist_frac=cat["generalist_frac"])
        n = raw01.shape[0]
        raw = np.stack([raw01[:, 0], raw01[:, 1] * 500 + 1,
                        raw01[:, 2] * 20 + 0.1] + [raw01[:, j]
                                                   for j in range(3, 8)], 1)
        tt_m = np.zeros((n, N_TT), bool)
        tt_m[np.arange(n), tt] = True
        dm_m = np.zeros((n, N_DM), bool)
        dm_m[np.arange(n), dm] = True
        names = [f"{cat['prefix']}{i}" for i in range(n)]
        return names, raw, tt_m, dm_m, gen, raw01, [None] * n
    rows = cat["entries"]
    raw = np.array([[e["raw_metrics"][k] for k in RAW_KEYS] for e in rows],
                   np.float64)
    tt_m = np.array([[t in e["task_types"] for t in inputs.TASK_TYPES]
                     for e in rows])
    dm_m = np.array([[d in e["domains"] for d in inputs.DOMAINS]
                     for e in rows])
    gen = np.array([e["generalist"] for e in rows])
    return ([e["name"] for e in rows], raw, tt_m, dm_m, gen, None,
            [e.get("backend") for e in rows])


def _register(mres, names, raw, raw01, tt_m, dm_m, gen, runners):
    from repro.core.mres import ModelEntry
    if raw01 is not None:
        metrics = [inputs.mega_raw_metrics(v) for v in raw01]
    else:
        metrics = [dict(zip(RAW_KEYS, map(float, r))) for r in raw]
    tts = [tuple(inputs.TASK_TYPES[j] for j in np.flatnonzero(r))
           for r in tt_m]
    dms = [tuple(inputs.DOMAINS[j] for j in np.flatnonzero(r)) for r in dm_m]
    mres.register_many([
        ModelEntry(name=names[i], raw_metrics=metrics[i], task_types=tts[i],
                   domains=dms[i], generalist=bool(gen[i]),
                   runner=runners[i])
        for i in range(len(names))])


def build(cfg: dict, seed: int, cache_dir: pathlib.Path) -> System:
    from repro.core.analyzer import AnalyzerConfig, TaskAnalyzer
    from repro.core.mres import MRES
    from repro.core.orchestrator import OptiRoute
    from repro.serving.engine import ServingEngine
    from repro.serving.runner import ModelRunner

    b = cfg.get("backend")
    arch = backend.of(b) if b is not None else None
    a = cfg["analyzer"]
    an_params = weights.analyzer_weights(a, cache_dir)
    analyzer = TaskAnalyzer(AnalyzerConfig(
        vocab_size=a["vocab_size"], d_model=a["d_model"],
        n_layers=a["n_layers"], n_heads=a["n_heads"], d_ff=a["d_ff"],
        max_len=a["max_len"], prune_head=a["prune_head"],
        prune_tail=a["prune_tail"], prune_mid=a["prune_mid"]))
    analyzer.params = jax.tree_util.tree_map(jax.numpy.asarray, an_params)

    names, raw, tt_m, dm_m, gen, raw01, backends = _catalog_rows(cfg, seed)
    runner = bw = None
    if arch is not None:
        mc = arch.program_config(b)
        bw = arch.make_weights(b, seed, mc.vocab_padded)
        runner = ModelRunner(mc, params=arch.program_params(bw))
    runners = [runner if be is not None else None for be in backends]
    mres = MRES()
    _register(mres, names, raw, raw01, tt_m, dm_m, gen, runners)
    mres.snapshot()

    r = cfg["routing"]
    router = OptiRoute(mres, analyzer, knn_k=r["knn_k"],
                       use_kernel=r["kernel"] == "pallas")
    router.engine.confidence_threshold = r["confidence_threshold"]
    router.engine.quantize = r["quantize"]
    engine = ServingEngine(router, prompt_len=cfg["engine"]["prompt_len"],
                           vocab_hash=cfg["engine"]["vocab_hash"])
    emb = reference.normalize_metrics(raw)
    return System(
        config=cfg, router=router, engine=engine, names=names,
        row_of={n: i for i, n in enumerate(names)},
        ref_catalog=reference.Catalog(emb, tt_m, dm_m, gen),
        analyzer_params=an_params, backend=b,
        backend_name=b["model"] if b else "", backend_weights=bw,
        runner=runner,
        on_chip={n for n, be in zip(names, backends) if be is not None})


def weights_vector(w: dict) -> np.ndarray:
    """A user's preference weights as the routing axes read them
    (missing metrics weigh 0.25, clipped to [0, 1])."""
    return np.clip(np.array([float(w.get(m, 0.25)) for m in inputs.METRICS],
                            np.float32), 0.0, 1.0)
