"""The benchmark's frozen copies of the input generators reproduce the
program's originals at fixed seeds."""
import pathlib
import sys

import numpy as np
import pytest

_BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_BENCH), str(_BENCH.parent), str(_BENCH.parent / "src")]

from benchlib import inputs, reference, tokenize  # noqa: E402


@pytest.mark.parametrize("seed,long_frac", [(0, 0.0), (7, 0.3), (123, 0.1)])
def test_make_workload_copy(seed, long_frac):
    from repro.data.workload import make_workload
    ours = inputs.make_workload(200, seed=seed, long_frac=long_frac)
    theirs = make_workload(200, seed=seed, long_frac=long_frac)
    assert [q.text for q in ours] == [r.text for r in theirs]
    assert [(q.task_type, q.domain, q.complexity) for q in ours] == [
        (r.sig.task_type, r.sig.domain, r.sig.complexity) for r in theirs]


@pytest.mark.parametrize("seed", [0, 5])
def test_mega_catalog_copy(seed):
    from benchmarks.router_scale import _mega_catalog
    from repro.core.mres import normalize_catalog
    n = 3000
    m = _mega_catalog(n, seed=seed)
    raw, tt, dm, gen = inputs.mega_catalog_arrays(n, seed=seed)
    entries = m.entries
    assert [e.raw_metrics for e in entries] == [
        inputs.mega_raw_metrics(v) for v in raw]
    assert [e.task_types for e in entries] == [
        (inputs.TASK_TYPES[i],) for i in tt]
    assert [e.domains for e in entries] == [(inputs.DOMAINS[i],) for i in dm]
    assert [e.generalist for e in entries] == list(gen)
    # the reference normalizes the same rows to the same embeddings
    raw_prog = np.array([[e.raw_metrics[k] for k, _, _ in reference.RAW_AXES]
                         for e in entries])
    np.testing.assert_allclose(reference.normalize_metrics(raw_prog),
                               normalize_catalog(entries), atol=1e-7)


@pytest.mark.parametrize("seed", [0, 11])
def test_poisson_arrivals_copy(seed):
    from repro.data.workload import TrafficScenario, poisson_arrivals
    sc = TrafficScenario(duration_s=20.0, base_rate=30.0, burst_rate=90.0,
                         burst_start=0.25, burst_len=0.35, seed=seed)
    np.testing.assert_array_equal(
        inputs.poisson_arrivals(20.0, 30.0, 90.0, 0.25, 0.35, seed),
        poisson_arrivals(sc))


def test_metric_and_tag_order_match_the_program():
    from repro.core import preferences as P
    assert inputs.METRICS == P.METRICS
    assert inputs.TASK_TYPES == P.TASK_TYPES
    assert inputs.DOMAINS == P.DOMAINS


@pytest.mark.parametrize("max_len", [16, 96, 512])
def test_tokenizer_and_pruning_copy(max_len):
    from repro.core.analyzer import AnalyzerConfig, prune_text
    from repro.data.tokenizer import HashTokenizer
    texts = [q.text for q in inputs.make_workload(64, seed=3, long_frac=0.5)]
    np.testing.assert_array_equal(
        tokenize.Tokenizer(4096).encode_batch(texts, max_len),
        HashTokenizer(4096).encode_batch(texts, max_len))
    cfg = AnalyzerConfig()
    assert [tokenize.prune_text(t, cfg.prune_head, cfg.prune_tail,
                                cfg.prune_mid) for t in texts] == [
        prune_text(cfg, t) for t in texts]


def test_periodic_bursts_are_denser_inside_the_burst():
    ts = inputs.periodic_burst_arrivals(100.0, 20.0, 3.0, 10.0, 2.0, seed=1)
    phase = ts % 10.0
    inside = ((phase >= 4.0) & (phase < 6.0)).sum() / 2.0
    outside = ((phase < 4.0) | (phase >= 6.0)).sum() / 8.0
    assert inside > 2.0 * outside
    np.testing.assert_array_equal(
        ts, inputs.periodic_burst_arrivals(100.0, 20.0, 3.0, 10.0, 2.0, 1))
