"""A whole run of each cell at a size a CPU holds, the harness's look
for a chip skipped: sound runs come out correct, and runs with the
timed path broken underneath come out not correct.  Also: the entry
point refuses to run without a TPU, and in a directory that holds only
the benchmark."""
import copy
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

_BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = _BENCH.parent
sys.path[:0] = [str(_BENCH), str(ROOT / "src")]

from benchlib import check, harness  # noqa: E402


# a cell kept under bench/ and not yet in BENCHMARK.json (PERF.md §7
# says what it waits for): configuration, mix, end-to-end metrics, and
# per-layer metrics with their sources
PENDING = {"served-qwen2-open": (
    "tier4-qwen2", "chat-open",
    {"request_p50_ms": "ms", "setup_s": "s"},
    {"request_tail_p95_ms": ("ms", "host_clock"),
     "prefill_ms": ("ms", "device_trace"),
     "decode_step_ms": ("ms", "device_trace"),
     "decode_roofline": ("%", "device_trace"),
     "decode_mfu": ("%", "device_trace"),
     "idle_share.served": ("%", "device_trace")})}


def _cell(name):
    if name not in PENDING:
        return harness.load_cell(name, ROOT)
    conf, mix, e2e, layer = PENDING[name]
    return harness.Cell(
        name, json.loads((_BENCH / "configs" / f"{conf}.json").read_text()),
        json.loads((_BENCH / "traffic" / f"{mix}.json").read_text()), 1,
        [{"name": n, "unit": u} for n, u in e2e.items()],
        [{"name": n, "unit": u, "source": s}
         for n, (u, s) in layer.items()])


def tiny_cell(name):
    """The cell as its files state it, cut to a CPU's size: 4,000
    catalog entries, a two-layer 64-wide backend, short prompts and
    answers, a shortly trained analyzer, light load."""
    c = _cell(name)
    cfg = copy.deepcopy(c.config)
    cfg["analyzer"]["train"].update(steps=30, samples=512)
    if cfg["catalog"]["generator"] == "mega":
        cfg["catalog"]["entries"] = 4000
    if "backend" in cfg:
        cfg["backend"].update(hidden_size=64, num_hidden_layers=2,
                              num_attention_heads=4, num_key_value_heads=2,
                              intermediate_size=128, vocab_size=512)
        cfg["engine"]["prompt_len"] = 32
    mix = copy.deepcopy(c.mix)
    if "max_new" in mix:
        mix["max_new"] = min(mix["max_new"], 8)
    if "answers" in mix:
        mix["answers"].update(median=4, buckets=[2, 4, 8])
    if "prompt_tokens" in mix["texts"]:
        mix["texts"]["prompt_tokens"]["median"] = 30
    if "arrivals" in mix:
        mix["arrivals"]["rps"] = 10.0
    if "max_batch" in mix:
        mix["max_batch"] = min(mix["max_batch"], 4)
    if "clients" in mix:
        mix.update(clients=4, pool=64)
    if "batch" in mix:
        mix.update(batch=16, pool=64)
    c.config, c.mix = cfg, mix
    return c


def _run(name, seed, tamper=None, seconds=2.0, trace=False, cache=None,
         load="light"):
    """``load``: the tiny cell's light load; ``full_windows``, windows
    that fill as the chip's do, where a generate takes seconds (groups
    of several requests in one answer bucket); ``own``, the mix's own
    rate and window (the tiny cell's batch of 4, which the groups formed
    there do not fill)."""
    cell = tiny_cell(name)
    if load == "full_windows":
        cell.mix.update(max_wait_ms=200)
        cell.mix["arrivals"]["rps"] = 20.0
    elif load == "own":
        own = _cell(name).mix
        cell.mix.update(max_wait_ms=own["max_wait_ms"])
        cell.mix["arrivals"]["rps"] = own["arrivals"]["rps"]
    return harness.run_cell(cell, seed, seconds, trace,
                            t_process=time.perf_counter(), require_tpu=False,
                            cache_dir=cache, tamper=tamper)


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("bench_cache")


CELLS = [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]] + sorted(PENDING)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, cache):
    res = _run(name, 5, cache=cache)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    for v in res["metrics"].values():
        assert v["value"] > 0
    if "served" in name:
        assert "logit_gap_mean" in res["checks"]


def _alter_decisions(monkeypatch):
    """The fused decision program picks the wrong model: its answer is
    altered where it is produced."""
    from repro.kernels import ops
    real = ops.analyze_route_step

    def altered(*a, **k):
        out = real(*a, **k)
        n = np.asarray(a[3]).shape[0]
        out["model_idx"] = (out["model_idx"] + 1) % n
        return out

    monkeypatch.setattr(ops, "analyze_route_step", altered)


def _alter_tokens(monkeypatch):
    """The backend returns one wrong token per request."""
    from repro.serving.runner import ModelRunner
    real = ModelRunner.generate

    def altered(self, tokens, max_new=16):
        out = real(self, tokens, max_new=max_new)
        out.tokens = out.tokens.copy()
        out.tokens[:, -1] = (out.tokens[:, -1] + 7) % self.cfg.vocab_size
        return out

    monkeypatch.setattr(ModelRunner, "generate", altered)


@pytest.mark.parametrize("name", CELLS)
def test_altered_decision_is_caught(name, cache, monkeypatch):
    res = _run(name, 6, cache=cache,
               tamper=lambda s: _alter_decisions(monkeypatch))
    assert not res["correct"]
    assert res["checks"]["route_err"]["value"] > \
        res["checks"]["route_err"]["limit"]


@pytest.mark.parametrize("name", [c for c in CELLS if "served" in c])
def test_altered_token_is_caught(name, cache, monkeypatch):
    res = _run(name, 7, cache=cache,
               tamper=lambda s: _alter_tokens(monkeypatch))
    assert not res["correct"]
    assert res["checks"]["logit_gap_mean"]["value"] > \
        res["checks"]["logit_gap_mean"]["limit"]


def _stale_cache(sysm):
    """The backend's decode step returns its cache unchanged: no step
    writes the keys and values of the token it read."""
    real = sysm.runner._decode

    def stale(params, cache, batch):
        logits, tok, _ = real(params, cache, batch)
        return logits, tok, cache

    sysm.runner._decode = stale


def _half_batch(sysm):
    """The backend runs only the first half of each group it is handed
    and gives the other half the first half's tokens."""
    real = sysm.runner.generate

    def half(tokens, max_new=16):
        out = real(tokens[:(len(tokens) + 1) // 2], max_new=max_new)
        out.tokens = np.resize(out.tokens, (len(tokens),
                                            out.tokens.shape[1]))
        return out

    sysm.runner.generate = half


# seconds a generate of the tiny backend takes per answer token: the
# chip's qwen2-1.5b takes about 5 ms a decode step for answers 64 times
# as long, so a generate lasts about as long as there
CHIP_LIKE_S_PER_TOKEN = 0.32


def _chip_like(fault):
    """``fault`` behind a backend slowed to the chip's time a generate,
    so that at the mix's own load requests queue behind the generate in
    progress as they do there."""
    def plant(sysm):
        fault(sysm)
        inner = sysm.runner.generate

        def slow(tokens, max_new=16):
            time.sleep(CHIP_LIKE_S_PER_TOKEN * max_new)
            return inner(tokens, max_new=max_new)

        sysm.runner.generate = slow
    return plant


@pytest.mark.parametrize("fault,load,seconds", [
    (_stale_cache, "full_windows", 2.0),
    (_half_batch, "full_windows", 2.0),
    (_chip_like(_half_batch), "own", 6.0)],
    ids=["stale_cache", "half_batch", "half_batch_at_own_load"])
def test_served_fault_is_caught(fault, load, seconds, cache):
    groups = []

    def plant(sysm):
        fault(sysm)
        inner = sysm.runner.generate

        def count(tokens, max_new=16):
            groups.append(len(tokens))
            return inner(tokens, max_new=max_new)

        sysm.runner.generate = count

    res = _run("served-qwen2-open", 13, cache=cache, tamper=plant,
               seconds=seconds, load=load)
    # the backend was handed groups of several requests
    assert max(groups) >= 2
    assert not res["correct"]
    assert res["checks"]["logit_gap_mean"]["value"] > \
        res["checks"]["logit_gap_mean"]["limit"]


def test_trace_run_reports_device_window(cache):
    res = _run(CELLS[0], 8, cache=cache, trace=True)
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_trace_records_no_python_calls(tmp_path):
    """The traced window holds XLA's events and the benchmark's
    annotations, not an event per Python call (named ``$file:line``),
    which would slow the host path the trace measures."""
    import glob
    import jax
    from jax.profiler import ProfileData

    hooks = harness.TraceHooks(str(tmp_path))
    hooks.start()
    with jax.profiler.TraceAnnotation("bench.probe"):
        sum(abs(i) for i in range(100))
        jax.numpy.ones(3).block_until_ready()
    hooks.end()
    hooks.write()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = [e.name for p in ProfileData.from_file(path).planes
             for line in p.lines for e in line.events]
    assert "bench.probe" in names
    assert not [n for n in names if n.startswith("$")]


def test_trace_is_written_only_once_the_run_asks(tmp_path):
    """Closing the traced window collects the trace but writes nothing:
    writing it out stalls the serving thread, so the run writes it once
    every request of the window has its answer."""
    import jax

    hooks = harness.TraceHooks(str(tmp_path))
    hooks.start()
    jax.numpy.ones(3).block_until_ready()
    hooks.end()
    assert not list(tmp_path.rglob("*.xplane.pb"))
    hooks.write()
    assert len(list(tmp_path.rglob("*.xplane.pb"))) == 1


def test_request_median_takes_on_chip_answers_and_failures():
    """``request_p50_ms`` is the median, from each request's due time,
    over the requests the on-chip backend answered with tokens and the
    failed ones; answers from models off the chip do not count there,
    and make up ``decide_p50_ms`` with the failed ones."""
    from types import SimpleNamespace as NS
    from benchlib import drive

    def rec(due, wait, model, tokens=True):
        ans = NS(model=model, admission="admitted",
                 tokens=np.zeros(4, np.int32) if tokens else None)
        return drive.Record(spec=None, due=due, done=due + wait, answer=ans)

    t0 = 100.0
    chip = [rec(t0 + i, w, "qwen2-1.5b")
            for i, w in enumerate([0.9, 2.5, 1.3, 4.0, 1.1])]
    off = [rec(t0 + i, w, "mamba2-1.3b", tokens=False)
           for i, w in enumerate([0.01, 0.02, 0.015, 0.03])]
    failed = drive.Record(spec=None, due=t0 + 2, done=t0 + 9.0,
                          error="RuntimeError: planted")
    run = drive.Run(chip + off + [failed], t0, t0 + 51.0, [])
    res = harness.outcomes(NS(on_chip={"qwen2-1.5b"}), run)
    waits = np.array([0.9, 2.5, 1.3, 4.0, 1.1, 7.0]) * 1e3
    assert res["on_chip_requests"] == 5 and res["failed"] == 1
    assert res["request_p50_ms"] == pytest.approx(np.percentile(waits, 50))
    assert res["request_p95_ms"] == pytest.approx(np.percentile(waits, 95))
    # the decision's median: the requests that end at the decision, with
    # the failed one
    assert res["decide_p50_ms"] == pytest.approx(np.percentile(
        np.r_[np.array([0.01, 0.02, 0.015, 0.03]) * 1e3, 7.0e3], 50))


def test_traced_served_run_reads_its_host_metrics(cache):
    """A traced run of the served cell reads every per-layer metric it
    can from a CPU run: the client's tail.  The CPU's trace has no
    device plane, so the device readers find nothing and their metrics
    are left out (``test_bench_trace`` reads them on the reduction of
    a traced run of this tiny cell recorded on the chip)."""
    cell = tiny_cell("served-qwen2-open")
    assert len(cell.per_layer) == 6
    res = _run("served-qwen2-open", 12, cache=cache, trace=True)
    assert res["correct"], res["checks"]
    device = {m["name"] for m in cell.per_layer
              if m["source"] == "device_trace"}
    assert set(res["metrics"]) == {m["name"] for m in cell.per_layer} \
        - device
    assert res["metrics"]["request_tail_p95_ms"]["value"] > 0


def _bench_cmd(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def _no_result(p):
    for line in p.stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert "correct" not in obj


def test_run_refuses_without_a_tpu():
    p = _bench_cmd(ROOT)
    assert p.returncode != 0
    _no_result(p)
    assert "no TPU" in p.stderr


def test_run_refuses_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(_BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench_cmd(tmp_path)
    assert p.returncode != 0
    _no_result(p)


def test_control_reads_above_the_program(cache):
    """The control (the reference one precision step down) reads far
    above the program on the served cell's numbers at this size."""
    import calibrate
    out = calibrate.readings(tiny_cell(CELLS[1]), 9, 2.0, True, cache)
    prog, ctl = out["program"], out["control"]
    assert ctl["analyzer_err_mean"] > 10 * prog["analyzer_err_mean"]
    assert ctl["logit_gap_mean"] > 10 * max(prog["logit_gap_mean"], 1e-7)
    # the control, in the program's place, comes out not correct by the
    # comparison that decides ``correct``; the program comes out correct
    assert out["program_correct"] and not out["control_correct"]


@pytest.mark.parametrize("base,mix", [
    ("decide-1m-open", {"loop": "batches", "front": "route_all",
                        "batch": 16, "pool": 64,
                        "texts": {"long_frac": 0.1,
                                  "long_words": [200, 400]},
                        "prefs": {"tenants": [{"name": "t0", "share": 0.7},
                                              {"name": "t1", "share": 0.3}]},
                        "max_new": 8}),
    ("served-qwen2-open", {"loop": "clients", "front": "async",
                           "max_batch": 4, "max_wait_ms": 20, "clients": 4,
                           "pool": 64, "pool_seed": 3,
                           "texts": {"long_frac": 0.3,
                                     "long_words": [200, 400]},
                           "prefs": {"profiles": [{"accuracy": 1.0},
                                                  {"cheapness": 1.0}]},
                           "max_new": 8}),
])
def test_closed_loops_run_correct(base, mix, cache):
    """The generator's closed loops (one caller sending batches to
    ``route_all``; clients that each wait for their reply), for offline
    and saturated mixes, run and come out correct."""
    cell = tiny_cell(base)
    cell.mix = mix
    res = harness.run_cell(cell, 11, 2.0, False,
                           t_process=time.perf_counter(), require_tpu=False,
                           cache_dir=cache)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_unknown_architecture_fails_at_set_up(tmp_path):
    """A configuration whose backend names an architecture with no
    module fails when its cell is loaded, naming the path looked for."""
    from benchlib import backend
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((_BENCH / "configs" / "tier4-qwen2.json").read_text())
    cfg["backend"]["architecture"] = "no-such-architecture"
    (tmp_path / "cfg.json").write_text(json.dumps(cfg))
    spec["configs"].append({"name": "tier4-qwen2", "file": "cfg.json"})
    spec["workloads"].append({"name": "served-qwen2-open",
                              "config": "tier4-qwen2",
                              "traffic": "chat-open", "chips": 1})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    with pytest.raises(backend.UnknownArchitecture) as e:
        harness.load_cell("served-qwen2-open", tmp_path)
    assert str(backend.DIR / "no-such-architecture.py") in str(e.value)


def test_new_backend_is_taken_from_a_new_file(tmp_path, cache, monkeypatch):
    """A backend module written as one new file under the backends
    directory serves a cell whose configuration names it: the harness,
    the reference and the readers reach it with no other file edited."""
    from benchlib import backend
    d = tmp_path / "backends"
    d.mkdir()
    (d / "toy.py").write_text((backend.DIR / "dense.py").read_text() + '''
CALLS = []
_dense_logits = logits


def logits(w, b, tokens, *, control=False):
    CALLS.append(len(tokens))
    return _dense_logits(w, b, tokens, control=control)
''')
    cell = tiny_cell("served-qwen2-open")
    cell.config["backend"]["architecture"] = "toy"
    monkeypatch.setattr(backend, "DIR", d)
    res = harness.run_cell(cell, 14, 2.0, False,
                           t_process=time.perf_counter(), require_tpu=False,
                           cache_dir=cache)
    assert res["correct"], res["checks"]
    toy = backend.load("toy")
    assert toy.__file__ == str(d / "toy.py")
    assert toy.CALLS and "logit_gap_mean" in res["checks"]


def _waits_ms(recs, t1):
    return [((r.done if r.done is not None else t1 + 60.0) - r.due) * 1e3
            for r in recs]


def test_decide_median_is_over_requests_that_end_at_the_decision(cache):
    """``decide_p50_ms`` is the median over the requests that end at the
    decision.  On a run of decide-1m-open, where every request does, it
    is the median over all of them, as it was read before the split; on
    the served cell it takes the off-chip answers and leaves the on-chip
    ones to ``request_p50_ms``."""
    from benchlib import drive
    for name in CELLS:
        sysm, tr = harness.prepare(tiny_cell(name), 15, 2.0, cache)
        run = drive.run(sysm, tr, 2.0)
        res = harness.outcomes(sysm, run)
        chip = [r for r in run.records
                if getattr(r.answer, "model", None) in sysm.on_chip]
        off = [r for r in run.records if r not in chip]
        assert res["failed"] == 0
        assert res["decide_p50_ms"] == pytest.approx(
            np.percentile(_waits_ms(off, run.t1), 50), rel=1e-12)
        if not sysm.on_chip:
            assert not chip
            assert res["decide_p50_ms"] == pytest.approx(np.percentile(
                _waits_ms(run.records, run.t1), 50), rel=1e-12)
        else:
            assert chip and off
            assert res["request_p50_ms"] == pytest.approx(
                np.percentile(_waits_ms(chip, run.t1), 50), rel=1e-12)
