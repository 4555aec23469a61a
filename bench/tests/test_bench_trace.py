"""The trace reduction and the per-layer readers, on a small trace
recorded on a TPU v5e (two jitted programs called three times each
inside ``bench.outer`` / ``bench.inner`` annotations) and on
hand-built traces."""
import pathlib
import sys
from types import SimpleNamespace

import numpy as np
import pytest

_BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_BENCH), str(_BENCH.parent / "src")]

from benchlib import counts, harness, peaks, readers, trace  # noqa: E402
from benchlib.trace import Device, Event, Reduced  # noqa: E402

RECORDED = _BENCH / "tests" / "data" / "small_v5e.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    return trace.reduce_profile(ProfileData.from_file(str(RECORDED)))


def test_recorded_device_programs(recorded):
    assert list(recorded.devices) == ["/device:TPU:0"]
    mods = recorded.modules()
    assert [m.name for m in mods] == ["jit__lambda"] * 6
    # the matmul program (~112.5 us) and the reduction (~25 us) alternate
    durs = [m.dur for m in mods]
    assert durs[0::2] == [112517.0, 112555.0, 112617.0]
    assert durs[1::2] == [25051.0, 24852.0, 25056.0]


def test_recorded_busy_time_is_the_union_of_operations(recorded):
    dev = recorded.devices["/device:TPU:0"]
    assert dev.busy_ns == 410855.0
    assert recorded.busy_s() == pytest.approx(410855e-9)
    assert len(dev.gaps) == 11
    # ops of a program never overlap the program's neighbours
    assert all(mod == "jit__lambda" for mod, _ in dev.ops)
    assert {o.name for _, o in dev.ops} == {
        "copy-start", "copy-done", "fusion", "add_reduce_fusion"}


def test_recorded_host_spans_on_the_same_clock(recorded):
    outer = recorded.spans_named("outer")
    inner = recorded.spans_named("inner")
    assert len(outer) == len(inner) == 3
    for o, i in zip(outer, inner):
        assert o.start <= i.start and i.end <= o.end
    # every device program ran within a couple of ms of the outer span
    # that launched it (host and device clocks agree to about a ms)
    for m in recorded.modules()[::2]:
        assert any(trace.inside(m, o, readers.SLACK_NS) for o in outer)
    self_ms = readers.self_time_ms(outer, inner)
    want = np.mean([o.dur - i.dur for o, i in zip(outer, inner)]) / 1e6
    assert self_ms == pytest.approx(want)


def test_recorded_breakdown(recorded):
    b = trace.breakdown(recorded)
    assert b["device_ops"][0][0] == "jit__lambda:fusion"
    assert b["device_ops"][0][1] == pytest.approx(3 * 89997.5e-9, rel=1e-4)
    assert len(b["device_ops"]) == 4
    # the three longest gaps are the 10 ms host sleeps inside bench.outer
    assert [g[0] for g in b["idle_gaps"][:3]] == ["outer"] * 3
    assert all(g[1] > 0.010 for g in b["idle_gaps"][:3])


@pytest.mark.parametrize("raw,kind", [
    ("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop", "fusion"),
    ("%copy-start = (f32[2]) copy-start(f32[2] %x.1)", "copy-start"),
    ("%convolution.3 = bf16[4,4] convolution(...)", "convolution"),
    ("%add_reduce_fusion = f32[] fusion(%x)", "add_reduce_fusion"),
])
def test_op_kind(raw, kind):
    assert trace.op_kind(raw) == kind


def test_module_name_drops_the_program_id():
    assert trace.module_name("jit_serve_step(1234567)") == "jit_serve_step"
    assert trace.module_name("jit_f") == "jit_f"


# ----------------------------------------------------------------------
# readers on hand-built traces
# ----------------------------------------------------------------------

def _reduced(modules, spans, window_ns):
    dev = Device(modules=sorted(modules, key=lambda e: e.start),
                 ops=[("", Event("fusion", m.start, m.dur)) for m in modules])
    dev.busy_ns = sum(m.dur for m in modules)
    return Reduced({"/device:TPU:0": dev}, sorted(spans, key=lambda e: e.start),
                   0.0, window_ns, window=window_ns)


def _ctx(red, **kw):
    ctx = SimpleNamespace(reduced=red, generate_calls=[],
                          peak=peaks.peak_for("TPU v5 lite"), **kw)
    return ctx


MS = 1e6


def test_route_host_time_excludes_the_device_program():
    spans = [Event("bench.route_all", 10 * MS, 8 * MS),
             Event("bench.route_all", 30 * MS, 6 * MS)]
    mods = [Event("jit_analyze_route_step_jit", 13 * MS, 3 * MS),
            Event("jit_analyze_route_step_jit", 32 * MS, 1 * MS)]
    red = _reduced(mods, spans, 100 * MS)
    read = harness.load_reader("route_host_ms")
    assert read(_ctx(red)) == pytest.approx(((8 - 3) + (6 - 1)) / 2)
    assert harness.load_reader("route_device_ms")(_ctx(red)) == \
        pytest.approx(2.0)
    assert harness.load_reader("idle_share.decide")(_ctx(red)) == \
        pytest.approx(96.0)


def test_serve_host_time_excludes_routing():
    spans = [Event("bench.serve_submit", 0, 50 * MS),
             Event("bench.route_all", 5 * MS, 10 * MS),
             Event("bench.serve_submit", 60 * MS, 20 * MS),
             Event("bench.route_all", 61 * MS, 4 * MS)]
    red = _reduced([], spans, 100 * MS)
    assert harness.load_reader("serve_host_ms")(_ctx(red)) == \
        pytest.approx(((50 - 10) + (20 - 4)) / 2)


def _served_trace():
    """Two generate calls: (B=2, 16-token prompt, 3 new tokens) and
    (B=4, same): an eager prefill, then decode steps with a small
    host-side add between them."""
    mods, spans, calls = [], [], []
    t = 1 * MS
    for B in (2, 4):
        s0 = t
        mods.append(Event("jit_analyze_route_step_jit", t - 0.5 * MS,
                          0.2 * MS))
        mods += [Event("jit_scan", t + 0.1 * MS, 4 * MS),
                 Event("jit_argmax", t + 4.2 * MS, 0.1 * MS)]
        t += 5 * MS
        for _ in range(2):
            mods.append(Event("jit_serve_step", t, 2 * MS))
            mods.append(Event("jit_add", t + 2.1 * MS, 0.01 * MS))
            t += 3 * MS
        spans.append(Event("bench.generate", s0, t - s0))
        calls.append((np.zeros((B, 16), np.int32),))
        t += 2 * MS
    return mods, spans, calls


def test_prefill_and_decode_readers():
    mods, spans, calls = _served_trace()
    red = _reduced(mods, spans, 40 * MS)
    m = {"hidden_size": 64, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "intermediate_size": 128, "vocab_size": 512, "qkv_bias": True}
    ctx = _ctx(red, system=SimpleNamespace(backend=m))
    ctx.generate_calls = calls
    assert harness.load_reader("prefill_ms")(ctx) == pytest.approx(4.1)
    assert harness.load_reader("decode_step_ms")(ctx) == pytest.approx(2.0)
    least = sum(readers.decode_least_s(ctx, B, 16, 2) for B in (2, 4))
    assert harness.load_reader("decode_roofline")(ctx) == pytest.approx(
        100 * least / 8e-3)
    flops = sum(counts.decode_step(m, B, 16 + j + 1).flops
                for B in (2, 4) for j in range(2))
    assert harness.load_reader("decode_mfu")(ctx) == pytest.approx(
        100 * flops / 8e-3 / ctx.peak.bf16_flops)


def test_readers_find_nothing_in_an_empty_trace():
    red = _reduced([], [], 10 * MS)
    ctx = _ctx(red, system=SimpleNamespace(backend=None))
    for name in ("route_host_ms", "route_device_ms", "serve_host_ms",
                 "prefill_ms", "decode_step_ms", "decode_roofline",
                 "decode_mfu"):
        assert harness.load_reader(name)(ctx) is None, name


# ----------------------------------------------------------------------
# the served cell's device readers on a traced run recorded on the chip
# ----------------------------------------------------------------------

SERVED = "served-qwen2-open"
# the reduction of a traced run of ``tiny_cell(SERVED)`` (seed 7, 3-s
# window) on a TPU v5e: its backend's dimensions, each device's programs
# and busy time, the ``bench.*`` spans, the traced window, the shapes of
# the traced ``generate`` calls, and the metrics that run reported
SERVED_RECORDED = _BENCH / "tests" / "data" / "served_tiny_v5e.json"


# the served cell's per-layer metrics read from the device's trace
SERVED_DEVICE = ["prefill_ms", "decode_step_ms", "decode_roofline",
                 "decode_mfu", "idle_share.served"]


@pytest.fixture(scope="module")
def served_recorded():
    import json
    rec = json.loads(SERVED_RECORDED.read_text())
    ev = lambda t: Event(*t)                                # noqa: E731
    devices = {k: Device(modules=[ev(m) for m in d["modules"]],
                         busy_ns=d["busy_ns"])
               for k, d in rec["devices"].items()}
    red = Reduced(devices, [ev(s) for s in rec["spans"]], 0.0, 0.0,
                  window=rec["window_ns"])
    ctx = _ctx(red, system=SimpleNamespace(backend=rec["backend"]))
    ctx.generate_calls = [(np.zeros(s, np.int32),)
                          for s in rec["generate_calls"]]
    return ctx, rec["metrics"]


@pytest.mark.parametrize("name", SERVED_DEVICE)
def test_served_device_reader_reads_the_recorded_run(name, served_recorded):
    """Each device reader of the served cell finds its value in the
    recorded run, the value that run reported; shares stay under 100%."""
    ctx, reported = served_recorded
    value = harness.load_reader(name)(ctx)
    assert value is not None and value > 0
    assert value == pytest.approx(reported[name], rel=1e-9)
    if name.endswith("roofline") or "mfu" in name or "share" in name:
        assert value < 100
