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
