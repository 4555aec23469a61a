"""Every seed gets the same work: a mix with ``pool_seed`` offers the
same requests at the same times in another order, and a configuration
with ``catalog.seed`` serves the same registry."""
import copy
import json
import pathlib
import sys

import numpy as np
import pytest

_BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_BENCH), str(_BENCH.parent / "src")]

from benchlib import system, traffic  # noqa: E402

SPEC = json.loads((_BENCH.parent / "BENCHMARK.json").read_text())
SEEDS = (5, 3_000_000_001)


def _mix(name):
    return json.loads((_BENCH / "traffic" / f"{name}.json").read_text())


def _config(name):
    return json.loads((_BENCH / "configs" / f"{name}.json").read_text())


def _requests(tr):
    return sorted((s.text, s.tenant, tuple(sorted(s.weights.items())))
                  for s in tr.specs)


@pytest.mark.parametrize("name", sorted(
    f.stem for f in (_BENCH / "traffic").glob("*.json")
    if "pool_seed" in _mix(f.stem)))
def test_pooled_mix_offers_the_same_requests_in_another_order(name):
    a, b = (traffic.build(_mix(name), s, 12.0) for s in SEEDS)
    np.testing.assert_array_equal(a.arrivals, b.arrivals)
    assert _requests(a) == _requests(b)
    assert [s.text for s in a.specs] != [s.text for s in b.specs]


@pytest.mark.parametrize("name", sorted(
    {c["name"] for c in SPEC["configs"]
     if "seed" in _config(c["name"]).get("catalog", {})}))
def test_fixed_catalog_seed_serves_one_registry(name):
    cfg = copy.deepcopy(_config(name))
    cfg["catalog"]["entries"] = 3000
    a, b = (system._catalog_rows(cfg, s) for s in SEEDS)
    for x, y in zip(a[1:5], b[1:5]):
        np.testing.assert_array_equal(x, y)
    del cfg["catalog"]["seed"]
    c, d = (system._catalog_rows(cfg, s) for s in SEEDS)
    assert not np.array_equal(c[1], d[1])


def _lengths(mix, seed, seconds=51.0):
    from benchlib.tokenize import Tokenizer
    tr = traffic.build(mix, seed, seconds)
    tok = Tokenizer(4096)
    return tr, [len(tok.encode(s.text, 1 << 20)) for s in tr.specs]


@pytest.mark.parametrize("name", sorted(
    f.stem for f in (_BENCH / "traffic").glob("*.json")
    if "prompt_tokens" in _mix(f.stem)["texts"]))
def test_drawn_prompt_lengths_keep_the_median_of_the_file(name):
    """Each prompt is as many tokens long as drawn, the pool's median is
    the file's, and every seed gets the same set of lengths."""
    mix = _mix(name)
    p = mix["texts"]["prompt_tokens"]
    a, la = _lengths(mix, SEEDS[0])
    b, lb = _lengths(mix, SEEDS[1])
    assert sorted(la) == sorted(lb)
    want = traffic.lognormal_lengths(len(la), p["median"], p["sigma"])
    assert sorted(la) == want.tolist()
    assert abs(np.median(la) - p["median"]) <= 0.01 * p["median"]
    # the spread is the file's: the quartiles lie sigma * 0.674 out
    q1, q3 = np.percentile(la, [25, 75])
    assert np.log(q3 / q1) / 2 == pytest.approx(0.6745 * p["sigma"],
                                                rel=0.1)


ANSWER_MIXES = sorted(f.stem for f in (_BENCH / "traffic").glob("*.json")
                      if "answers" in _mix(f.stem))


@pytest.mark.parametrize("name", ANSWER_MIXES)
def test_answers_land_only_in_the_buckets(name):
    mix = _mix(name)
    a = mix["answers"]
    tr = traffic.build(mix, SEEDS[1], 51.0)
    got = [s.max_new for s in tr.specs]
    assert set(got) == set(a["buckets"])
    # each length goes to its nearest bucket in log space: a bucket
    # holds the drawn lengths between the geometric means with its
    # neighbours
    drawn = traffic.lognormal_lengths(len(got), a["median"], a["sigma"])
    b = sorted(a["buckets"])
    for lo, hi in zip(b, b[1:]):
        edge = np.sqrt(lo * hi)
        assert sum(g <= lo for g in got) == sum(d < edge for d in drawn)
    # the median answer lies in the bucket nearest the file's median
    assert sorted(got)[len(got) // 2] == min(
        b, key=lambda x: abs(np.log(x / a["median"])))


@pytest.mark.parametrize("name", ANSWER_MIXES)
def test_served_answer_lengths_keep_the_median_and_mean(name):
    """The buckets keep the drawn lengths' median to 1% and their mean
    to 7% (the longest bucket caps the tail): the backend generates
    about as many tokens as the file's distribution asks."""
    mix = _mix(name)
    a = mix["answers"]
    for seed in SEEDS:
        got = [s.max_new for s in traffic.build(mix, seed, 51.0).specs]
        drawn = traffic.lognormal_lengths(len(got), a["median"], a["sigma"])
        assert np.median(got) == pytest.approx(np.median(drawn), rel=0.01)
        assert np.mean(got) == pytest.approx(np.mean(drawn), rel=0.07)


# sha256 of each seed's request pool of decide-1m-open (id, text,
# tenant, weights, max_new, in arrival order, for a 51-s window) and
# of its arrival times, as the generator gave them before it learnt
# per-request lengths
DECIDE_POOL = {
    5: "5d017c2fa6954dc4f5c9a2de1a450f4eb766fc14bd7d203dda54dadc5dcd5b7e",
    3_000_000_001:
        "c6e81906724a272da3f3b37952302df62867599a2c49ea164f0452d29f9aeeb0"}
DECIDE_ARRIVALS = "5f9b2cac1ec01ec6"


@pytest.mark.parametrize("seed", sorted(DECIDE_POOL))
def test_decide_pool_is_as_before(seed):
    import hashlib
    mix = _mix(next(w["traffic"] for w in SPEC["workloads"]
                    if w["name"] == "decide-1m-open"))
    tr = traffic.build(mix, seed, 51.0)
    blob = json.dumps([[s.id, s.text, s.tenant, sorted(s.weights.items()),
                        s.max_new] for s in tr.specs])
    assert len(tr.specs) == 1476
    assert hashlib.sha256(blob.encode()).hexdigest() == DECIDE_POOL[seed]
    assert hashlib.sha256(tr.arrivals.tobytes()).hexdigest()[:16] == \
        DECIDE_ARRIVALS
