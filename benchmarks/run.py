"""Benchmark harness: one experiment per paper claim (DESIGN.md §6).

  PYTHONPATH=src:. python -m benchmarks.run [--only name] [--smoke]

Prints a ``name,us_per_call,derived`` CSV summary (plus per-benchmark
detail above it) and writes JSON payloads to results/bench/.

``--smoke`` runs the seconds-scale CI variants of every benchmark that
has one (routing throughput, adaptive regret, load-aware SLO, semantic
cache hit path) — the CI slow job's entry point.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks import (ablations, adaptive, analyzer_pruning, batch_mode,
                        cache_hit, feedback, load_aware, merging,
                        obs_overhead, roofline, router_scale, routing_win,
                        soak)
from repro.launch.compile_cache import enable_compile_cache

ALL = {
    "routing_win": routing_win.run,
    "batch_mode": batch_mode.run,
    "feedback": feedback.run,
    "adaptive": adaptive.run,
    "load_aware": load_aware.run,
    "cache_hit": cache_hit.run,
    "router_scale": router_scale.run,
    "obs_overhead": obs_overhead.run,
    "analyzer_pruning": analyzer_pruning.run,
    "merging": merging.run,
    "ablations": ablations.run,
    "roofline": roofline.run,
    "soak": soak.run,
}

# benchmarks with a seconds-scale CI mode (each main accepts --smoke)
SMOKE = {
    "router_scale": router_scale.main,
    "adaptive": adaptive.main,
    "load_aware": load_aware.main,
    "cache_hit": cache_hit.main,
    "obs_overhead": obs_overhead.main,
    "analyzer_pruning": analyzer_pruning.main,
    "soak": soak.main,
}


def _run_smoke(names) -> int:
    failed = []
    for name in names:
        print(f"[bench-smoke] {name} ...", flush=True)
        t0 = time.time()
        try:
            rc = SMOKE[name](["--smoke"])
            if rc:
                failed.append(name)
        except Exception:                      # noqa: BLE001
            traceback.print_exc()
            failed.append(name)
        print(f"[bench-smoke] {name} done in {time.time() - t0:.1f}s\n",
              flush=True)
    if failed:
        print(f"\nFAILED: {failed}", file=sys.stderr)
        return 1
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", action="append", default=None,
                    choices=list(ALL))
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale CI variants (subset of "
                    f"{sorted(SMOKE)})")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.smoke:
        names = args.only or list(SMOKE)
        missing = [n for n in names if n not in SMOKE]
        if missing:            # refuse a silent green no-op
            ap.error(f"no --smoke variant for {missing}; "
                     f"available: {sorted(SMOKE)}")
        return _run_smoke(names)
    names = args.only or list(ALL)

    rows = []
    failed = []
    for name in names:
        print(f"[bench] {name} ...", flush=True)
        t0 = time.time()
        try:
            rows.append(ALL[name]())
        except Exception:                      # noqa: BLE001
            traceback.print_exc()
            failed.append(name)
            rows.append((name, 0.0, "FAILED"))
        print(f"[bench] {name} done in {time.time() - t0:.1f}s\n",
              flush=True)

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.2f},{derived}")
    if failed:
        print(f"\nFAILED: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
