"""One run of one cell: set up, warm up, measure, trace, check.

``run_cell`` is driven by data alone: the cell names a configuration
file and a traffic mix file, and the per-layer metrics are readers
under ``bench/metrics/`` found by their names in ``BENCHMARK.json``.
"""
from __future__ import annotations

import gc
import json
import pathlib
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import numpy as np

from benchlib import backend, check, drive, peaks, system as sysmod, traffic

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class NoChip(RuntimeError):
    pass


@dataclass
class Cell:
    name: str
    config: dict
    mix: dict
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cell = next((w for w in spec["workloads"] if w["name"] == name), None)
    if cell is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config = json.loads((root / conf["file"]).read_text())
    if "backend" in config:
        backend.of(config["backend"])       # UnknownArchitecture if none
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                     .read_text())

    def mine(m):
        return name in m.get("workloads", [name])

    return Cell(name, config, mix, cell["chips"],
                [m for m in spec["end_to_end"] if mine(m)],
                [m for m in spec["per_layer"] if mine(m)])


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    if require_tpu:
        if d.platform != "tpu":
            raise NoChip(f"no TPU visible (platform {d.platform!r})")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips, found {len(devs)}")
        peaks.peak_for(d.device_kind)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": min(len(devs), chips)}


def memory_peak(chips: int) -> int:
    import jax
    best = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        best = max(best, int(stats.get("peak_bytes_in_use", 0)))
    return best


# ----------------------------------------------------------------------
# compilations inside the window
# ----------------------------------------------------------------------

class CompileCounter:
    """Counts programs compiled, or loaded from the persistent cache,
    while ``armed``: a shape that was not warmed up, or a program the
    program under test builds anew on every call."""

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0              # compiled, or loaded from the cache
        self.cache_hits = 0         # of those, loaded from the cache
        self.names: List[str] = []
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if self.armed and name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def _duration(self, name, _secs, fun_name=None, **_):
        if self.armed and name == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.names.append(str(fun_name))


# ----------------------------------------------------------------------
# spans around the calls the benchmark makes into each layer
# ----------------------------------------------------------------------

def _annotate(obj, attr: str, span: str, log: Optional[list] = None):
    import jax
    fn = getattr(obj, attr)

    def wrapped(*args, **kwargs):
        with jax.profiler.TraceAnnotation("bench." + span):
            if log is not None:
                log.append(args)
            return fn(*args, **kwargs)

    setattr(obj, attr, wrapped)


TRACE_S = 15.0      # the traced part of a window, from its start


class TraceHooks(drive.Hooks):
    """Profiles the first ``TRACE_S`` seconds of the window (or all of
    a shorter one): a short trace keeps the profiler's own buffers small
    beside a backend that fills the chip, and the reduction fast.

    The trace is collected when that time is up and written to ``dir``
    by ``write``, which the run calls once every request of the window
    has its answer: on a TPU v5e, writing out 15 s of the served cell's
    trace held its serving thread for ~77 s, while collecting it did
    not.  The Python tracer stays off: no reader uses its events, and
    recording every Python call slows the host path being measured."""

    def __init__(self, trace_dir: str):
        self.dir = trace_dir
        self.traced_s = 0.0
        self._lock = threading.Lock()
        self._t0 = None
        self._timer = None
        self._session = None
        self._xspace = None

    def start(self):
        import jax
        # the session jax.profiler.start_trace opens, whose stop_trace
        # would collect and write in one call
        from jax._src.lib import _profiler
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.devices()               # the TPU tracer needs the backend up
        self._session = _profiler.ProfilerSession(opts)
        self._t0 = time.perf_counter()
        self._timer = threading.Timer(TRACE_S, self._stop)
        self._timer.start()

    def _stop(self):
        with self._lock:
            if self._t0 is None:
                return
            self.traced_s = time.perf_counter() - self._t0
            self._t0 = None
            self._xspace = self._session.stop()

    def end(self):
        if self._timer is not None:
            self._timer.cancel()
            self._timer.join()
        self._stop()

    def write(self):
        """Write the collected trace to ``dir`` as ``*.xplane.pb``."""
        self._session.export(self._xspace, self.dir)
        self._xspace = None


class CountHooks(drive.Hooks):
    def __init__(self, counter: CompileCounter, inner: drive.Hooks):
        self.counter, self.inner = counter, inner

    def start(self):
        self.inner.start()
        self.counter.armed = True

    def end(self):
        self.counter.armed = False
        self.inner.end()


# ----------------------------------------------------------------------
# warm-up: exactly the shapes this cell's traffic produces
# ----------------------------------------------------------------------

def _q_buckets(max_rows: int) -> List[int]:
    """Batch sizes that reach every power-of-two routing bucket (floor
    8) a window of up to ``max_rows`` requests can use."""
    out, b = [], 8
    while True:
        out.append(min(b, max_rows))
        if b >= max_rows:
            return sorted(set(out))
        b *= 2


def warm_up(sysm, tr: traffic.Traffic) -> None:
    mix = tr.mix
    specs = tr.specs
    if mix["front"] == "route_all":
        B = mix["batch"]
        texts = [s.text for s in specs[:B]]
        sysm.router.route_all(texts, [drive.make_request(sysm, s, {}).prefs
                                      for s in specs[:B]])
        return
    prefs: dict = {}
    for b in _q_buckets(mix["max_batch"]):
        sysm.engine.submit([drive.make_request(sysm, s, prefs)
                            for s in specs[:b]])
    if sysm.runner is not None:
        # every answer length of the traffic x every group size the
        # backend can be handed in one window
        prompts = check.prompt_tokens(sysm, [s.text for s in specs])
        for max_new in sorted({s.max_new for s in specs}):
            for g in range(1, mix["max_batch"] + 1):
                toks = np.resize(prompts, (g, prompts.shape[1]))
                sysm.runner.generate(toks, max_new=max_new)


# ----------------------------------------------------------------------
# end-to-end metrics
# ----------------------------------------------------------------------

def _pct_ms(lat: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(lat) * 1e3, q)) if lat else 0.0


def outcomes(sysm, run: drive.Run) -> dict:
    """Per-request outcome: attempted, failed, latencies, on-chip.

    The decision's latencies are over the requests that end at the
    decision: all but those the on-chip backend answered.  The
    request's latencies are over those the on-chip backend answered,
    with the failed ones where the cell has such a backend.  A failed
    request counts in both with the time it waited."""
    att = fail = 0
    lat_dec, lat_chip = [], []
    done_in, tok_in = 0, 0
    chip_n = 0
    for r in run.records:
        att += 1
        ans = r.answer
        ok = r.done is not None and not r.error and ans is not None
        if ok and hasattr(ans, "admission"):
            ok = ans.admission in ("admitted", "rerouted")
        waited = (r.done if r.done is not None else run.t1 + drive.GRACE_S) \
            - r.due
        on_chip = ok and getattr(ans, "model", "") in sysm.on_chip \
            and getattr(ans, "tokens", None) is not None
        if not ok:
            fail += 1
        if not on_chip:
            lat_dec.append(waited)
        if on_chip or (not ok and sysm.on_chip):
            lat_chip.append(waited)
        if on_chip:
            chip_n += 1
        if ok and r.done <= run.t1:
            done_in += 1
            if on_chip:
                tok_in += len(ans.tokens)
    secs = run.t1 - run.t0
    return {"attempted": att, "failed": fail,
            "decide_p50_ms": _pct_ms(lat_dec, 50),
            "decide_p95_ms": _pct_ms(lat_dec, 95),
            "decisions_per_s": done_in / secs,
            "request_p50_ms": _pct_ms(lat_chip, 50),
            "request_p95_ms": _pct_ms(lat_chip, 95),
            "out_tokens_per_s": tok_in / secs,
            "on_chip_share": chip_n / max(att, 1),
            "on_chip_requests": chip_n}


def failure_reasons(run: drive.Run, top: int = 5) -> List[str]:
    """The first few distinct reasons requests failed or never came."""
    out: List[str] = []
    for r in run.records:
        adm = getattr(r.answer, "admission", "admitted")
        why = r.error or getattr(r.answer, "error", "") or (
            "no answer" if r.done is None else "")
        if not why and adm in ("admitted", "rerouted"):
            continue
        why = f"{adm}: {why}"[:200]
        if why not in out:
            out.append(why)
        if len(out) >= top:
            break
    return out


# ----------------------------------------------------------------------
# per-layer metric readers
# ----------------------------------------------------------------------

@dataclass
class Context:
    cell: Cell
    system: object
    run: drive.Run
    reduced: object                # trace.Reduced
    peak: peaks.Peak
    generate_calls: List[tuple] = field(default_factory=list)


def load_reader(name: str) -> Callable:
    return backend.import_file(BENCH / "metrics" / f"{name}.py",
                               "bench_metric_").read


# ----------------------------------------------------------------------
# the run
# ----------------------------------------------------------------------

def _log(**fields) -> None:
    print(json.dumps(fields), flush=True)


def enable_cache() -> str:
    """JAX's persistent compilation cache, at the program's fixed path
    in the checkout (or ``JAX_COMPILATION_CACHE_DIR``), keeping every
    program so that only a checkout's first run compiles."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache


def prepare(cell: Cell, seed: int, seconds: float,
            cache_dir: Optional[pathlib.Path] = None, warm: bool = True):
    """Build the system and the traffic from ``seed`` and warm up (a
    process that has already run the same shapes may skip it)."""
    sysm = sysmod.build(cell.config, seed, cache_dir or ROOT / ".bench_cache")
    tr = traffic.build(cell.mix, seed, seconds)
    if warm:
        warm_up(sysm, tr)
    return sysm, tr


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_process: float, require_tpu: bool = True,
             cache_dir: Optional[pathlib.Path] = None,
             tamper: Optional[Callable] = None) -> dict:
    """Run one cell once and return its result object.  ``tamper``
    (tests only) receives the built system before the window."""
    dev = device_info(cell.chips, require_tpu)
    peak = peaks.peak_for(dev["kind"]) if require_tpu \
        else peaks.peak_for("TPU v5 lite")
    cache = enable_cache()
    counter = CompileCounter()
    sysm, tr = prepare(cell, seed, seconds, cache_dir)
    if tamper is not None:
        tamper(sysm)

    gen_calls: List[tuple] = []
    tmp = None
    hooks: drive.Hooks = drive.Hooks()
    if trace:
        tmp = tempfile.mkdtemp(prefix="bench-trace-")
        hooks = TraceHooks(tmp)
        _annotate(sysm.engine, "submit", "serve_submit")
        _annotate(sysm.router, "route_all", "route_all")
        if sysm.runner is not None:
            _annotate(sysm.runner, "generate", "generate", gen_calls)
    setup_s = time.perf_counter() - t_process
    gc0 = [g["collections"] for g in gc.get_stats()]
    run = drive.run(sysm, tr, seconds, CountHooks(counter, hooks))
    gcs = [g["collections"] - c for g, c in zip(gc.get_stats(), gc0)]
    mem = memory_peak(cell.chips)
    res = outcomes(sysm, run)
    rows = [sysm.row_of[m] for m in (getattr(r.answer, "model", None)
                                     for r in run.records)
            if m in sysm.row_of]
    late = np.asarray(run.lateness) * 1e3 if run.lateness else np.zeros(1)
    _log(info="window", workload=cell.name, seed=seed, seconds=seconds,
         compile_cache=cache, compiles_in_window=counter.count,
         compiled_in_window=sorted(set(counter.names))[:20],
         cache_loads_in_window=counter.cache_hits,
         generator_late_ms_p99=float(np.percentile(late, 99)),
         generator_late_ms_max=float(late.max()),
         attempted=res["attempted"], failed=res["failed"],
         decide_p50_ms=res["decide_p50_ms"],
         decide_p95_ms=res["decide_p95_ms"],
         request_p95_ms=res["request_p95_ms"],
         on_chip_share=res["on_chip_share"],
         on_chip_requests=res["on_chip_requests"],
         routed_models=len(set(rows)),
         routed_row_mean=float(np.mean(rows)) if rows else 0.0,
         gc_collections_in_window=gcs,
         failures=failure_reasons(run))

    e2e = {"setup_s": setup_s, **{k: res[k] for k in (
        "decide_p50_ms", "decide_p95_ms", "decisions_per_s",
        "request_p50_ms", "request_p95_ms", "out_tokens_per_s")}}
    out_dev = {**dev, "memory_peak_bytes": mem}
    breakdown = None
    if trace:
        from benchlib import trace as tracemod
        hooks.write()
        red = tracemod.load(tmp)
        red.window = hooks.traced_s * 1e9
        shutil.rmtree(tmp, ignore_errors=True)
        ctx = Context(cell, sysm, run, red, peak, gen_calls)
        metrics = {}
        for m in cell.per_layer:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out_dev["busy_s"] = red.busy_s()
        out_dev["window_s"] = red.window_ns() / 1e9
        breakdown = tracemod.breakdown(red)
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    # the program's state is freed before the reference runs
    gen_calls.clear()
    checks = check.check_run(sysm, run, seed)
    result = {"correct": check.verdict(checks, res["failed"]), "attempted": res["attempted"],
              "failed": res["failed"], "metrics": metrics, "device": out_dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return result
