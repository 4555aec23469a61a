"""The program's own ``repro.*`` spans in the profiler trace: captured on
the CPU around ``AsyncServingEngine`` windows, recorded on a TPU v5e,
and read by the readers of ``queue_wait_ms``, ``catalog_lookup_ms``,
``encode_ms`` and ``idle_host_share.decide`` on hand-built traces.

The recorded trace (``data/spans_v5e.xplane.pb``) is made on the chip by
running this file as a script:

  python bench/tests/test_bench_program_spans.py <out_dir>
"""
import asyncio
import glob
import os
import pathlib
import sys
import tempfile

import pytest

_BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_BENCH), str(_BENCH.parent / "src")]

from benchlib import harness, program_spans, readers, trace  # noqa: E402
from benchlib.program_spans import Span  # noqa: E402
from benchlib.trace import Device, Event, Reduced  # noqa: E402

RECORDED = _BENCH / "tests" / "data" / "spans_v5e.xplane.pb"
WINDOW = 3          # requests per window


def capture(trace_dir, *, tracer=None, windows=1, annotate=False):
    """Serve ``windows`` windows of ``WINDOW`` requests through an
    ``AsyncServingEngine`` (the 10-model catalog, a small analyzer with
    random weights: the fused analyze->route path) inside a profiler
    session, and return the engine.  ``annotate`` wraps
    ``engine.submit`` and ``router.route_all`` in the benchmark's
    ``bench.*`` spans, as a traced benchmark run does."""
    import jax
    from repro.core.analyzer import AnalyzerConfig, TaskAnalyzer
    from repro.core.orchestrator import OptiRoute
    from repro.serving.async_engine import AsyncServingEngine
    from repro.serving.catalog import build_catalog
    from repro.serving.engine import Request, ServingEngine

    cfg = AnalyzerConfig(vocab_size=512, d_model=32, n_layers=1,
                         n_heads=2, d_ff=64, max_len=24)
    router = OptiRoute(build_catalog(), TaskAnalyzer(cfg, seed=0),
                       knn_k=4, tracer=tracer)
    engine = ServingEngine(router)
    reqs = [Request(text=f"summarize report {i} for the legal team",
                    prefs="balanced", id=i, max_new=2)
            for i in range(WINDOW * windows)]
    engine.submit(reqs[:WINDOW])            # compile outside the trace
    if annotate:
        harness._annotate(engine, "submit", "serve_submit")
        harness._annotate(router, "route_all", "route_all")

    async def drive():
        async with AsyncServingEngine(engine, max_batch=WINDOW,
                                      max_wait_ms=1000.0) as aeng:
            for w in range(windows):
                await asyncio.gather(*(aeng.submit(r) for r in
                                       reqs[w * WINDOW:(w + 1) * WINDOW]))

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0            # annotations and XLA alone
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        asyncio.run(drive())
    finally:
        jax.profiler.stop_trace()
    return engine


def _profile(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                        recursive=True)
    return ProfileData.from_file(path)


def _inside(inner, outer):
    return (inner.thread == outer.thread and outer.start <= inner.start
            and inner.end <= outer.end)


def _only(red, name):
    (s,) = program_spans.named(red, name)
    return s


# ----------------------------------------------------------------------
# captured on the CPU
# ----------------------------------------------------------------------

@pytest.mark.parametrize("with_tracer", [False, True],
                         ids=["no-tracer", "tracer"])
def test_window_spans_nest_in_the_profiler_trace(tmp_path, with_tracer):
    from repro.obs import Tracer
    tr = Tracer() if with_tracer else None
    capture(tmp_path, tracer=tr)
    pd = _profile(tmp_path)
    red = trace.reduce_profile(pd)
    red.program_spans = program_spans.reduce(pd)
    # the benchmark's reduction reads what it read before: no bench.*
    # span was opened, and the program's spans are not among its spans
    assert red.spans == []
    window = _only(red, "window")
    submit = _only(red, "submit")
    route_all = _only(red, "route_all")
    generate = _only(red, "generate")
    assert _inside(submit, window)
    assert _inside(route_all, submit) and _inside(generate, submit)
    assert _inside(_only(red, "analyze"), route_all)
    assert _inside(_only(red, "route_step"), route_all)
    lookups = program_spans.named(red, "catalog_lookup")
    assert lookups and all(_inside(c, generate) for c in lookups)
    assert window.stats["size"] == WINDOW
    assert window.stats["backlog"] == 0
    assert 0.0 <= window.stats["wait_ms_max"] <= window.stats["wait_ms_sum"]
    # only the window span carries stats onto the annotation
    assert all(s.stats == {} for s in red.program_spans
               if s.name != "repro.window")
    if tr is None:
        return
    # the ring holds the same tree
    (root,) = [s for s in tr.spans() if s.name == "window"]
    tree = tr.summary_tree(root.trace_id)
    assert tree["attrs"]["size"] == WINDOW

    def shape(node):
        return (node["name"], sorted(shape(c) for c in node["children"]))

    assert shape(tree) == ("window", [("submit", [
        ("generate", [("catalog_lookup", [])] * len(lookups)),
        ("route_all", [("analyze", []), ("route_step", [])])])])


# ----------------------------------------------------------------------
# recorded on a TPU v5e (three windows, bench.* spans around submit and
# route_all as in a traced benchmark run)
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(RECORDED))
    red = trace.reduce_profile(pd)
    red.program_spans = program_spans.reduce(pd)
    return red


def test_recorded_programs_start_inside_their_route_step(recorded):
    steps = program_spans.named(recorded, "route_step")
    mods = readers.programs(recorded, readers.ROUTE_PROGRAM)
    assert len(mods) == len(steps) == 3
    for m, s in zip(mods, steps):
        assert trace.inside(m, s, readers.SLACK_NS)


def test_recorded_program_and_bench_spans_share_a_clock(recorded):
    pairs = [("serve_submit", "submit"), ("route_all", "route_all")]
    for bench_name, name in pairs:
        outer = recorded.spans_named(bench_name)
        inner = program_spans.named(recorded, name)
        assert len(outer) == len(inner) == 3
        for o, i in zip(outer, inner):
            assert o.start <= i.start and i.end <= o.end
    windows = program_spans.named(recorded, "window")
    assert [w.stats["size"] for w in windows] == [WINDOW] * 3


# ----------------------------------------------------------------------
# readers on hand-built traces
# ----------------------------------------------------------------------

MS = 1e6


def _reduced(spans, ops=(), window_ns=100 * MS):
    dev = Device(ops=[("", Event("fusion", s, d)) for s, d in ops])
    red = Reduced({"/device:TPU:0": dev}, [], 0.0, window_ns,
                  window=window_ns)
    red.program_spans = sorted(spans, key=lambda s: s.start)
    return red


def _read(name, red):
    return harness.load_reader(name)(type("Ctx", (), {"reduced": red}))


def _span(name, start_ms, dur_ms, thread=1, **stats):
    return Span("repro." + name, start_ms * MS, dur_ms * MS, thread, stats)


def test_queue_wait_is_the_mean_over_requests():
    red = _reduced([
        _span("window", 0, 10, size=2, wait_ms_sum=10.0, wait_ms_max=6.0,
              backlog=0),
        _span("window", 20, 10, size=3, wait_ms_sum=20.0, wait_ms_max=9.0,
              backlog=4),
        _span("submit", 21, 5)])
    assert _read("queue_wait_ms", red) == pytest.approx(30.0 / 5)


def test_catalog_lookup_sums_inside_each_submit():
    red = _reduced([
        _span("submit", 0, 20),
        _span("catalog_lookup", 2, 3), _span("catalog_lookup", 8, 4),
        _span("catalog_lookup", 9, 1, thread=2),     # another thread
        _span("submit", 30, 10),
        _span("catalog_lookup", 31, 2),
        _span("catalog_lookup", 50, 5)])             # in no submit
    assert _read("catalog_lookup_ms", red) == pytest.approx((7 + 2) / 2)


def test_encode_reads_the_analyze_span_of_each_route_all():
    red = _reduced([
        _span("route_all", 0, 6), _span("analyze", 0.5, 1.5),
        _span("route_step", 2.5, 1),
        _span("route_all", 10, 4), _span("analyze", 10.2, 0.5),
        _span("analyze", 20, 9)])                    # outside route_all
    assert _read("encode_ms", red) == pytest.approx((1.5 + 0.5) / 2)


def test_idle_host_share_counts_gaps_inside_windows_only():
    # windows 10-30 and 50-60 ms; the device runs 12-14, 20-40 and
    # 70-80 ms: idle inside windows 10-12, 14-20 and 50-60 = 18 ms; the
    # gaps 40-50 and 60-70 lie outside every window
    red = _reduced([_span("window", 10, 20), _span("window", 50, 10)],
                   ops=[(12 * MS, 2 * MS), (20 * MS, 20 * MS),
                        (70 * MS, 10 * MS)])
    assert _read("idle_host_share.decide", red) == pytest.approx(18.0)
    assert _read("idle_host_share.decide", red) <= readers.idle_pct(red)


def test_program_span_readers_find_nothing_without_program_spans():
    bare = Reduced({"/device:TPU:0": Device()}, [], 0.0, 10 * MS,
                   window=10 * MS)
    for red in (bare, _reduced([])):
        for name in ("queue_wait_ms", "catalog_lookup_ms", "encode_ms",
                     "idle_host_share.decide"):
            assert _read(name, red) is None, name


if __name__ == "__main__":
    out = pathlib.Path(sys.argv[1] if len(sys.argv) > 1
                       else tempfile.mkdtemp())
    capture(out, windows=3, annotate=True)
    print(glob.glob(os.path.join(str(out), "**", "*.xplane.pb"),
                    recursive=True))
