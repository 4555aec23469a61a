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


# a cell kept under bench/ whose limits still wait for their readings on
# the chip, and so is not yet in BENCHMARK.json: configuration, mix and
# end-to-end metrics
PENDING = {"served-qwen2-open": ("tier4-qwen2", "chat-open",
                                 {"request_p95_ms": "ms", "setup_s": "s"})}


def _cell(name):
    if name not in PENDING:
        return harness.load_cell(name, ROOT)
    conf, mix, e2e = PENDING[name]
    return harness.Cell(
        name, json.loads((_BENCH / "configs" / f"{conf}.json").read_text()),
        json.loads((_BENCH / "traffic" / f"{mix}.json").read_text()), 1,
        [{"name": n, "unit": u} for n, u in e2e.items()], [])


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
    mix["max_new"] = min(mix["max_new"], 8)
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


def _run(name, seed, tamper=None, seconds=2.0, trace=False, cache=None):
    return harness.run_cell(tiny_cell(name), seed, seconds, trace,
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


def test_trace_run_reports_device_window(cache):
    res = _run(CELLS[0], 8, cache=cache, trace=True)
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


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
