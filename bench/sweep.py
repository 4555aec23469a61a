"""Find an open-loop cell's knee: the highest base rate at which the
backlog does not grow over the window.

  python bench/sweep.py --workload <cell> --seed <n> \
      --rates 20,30,40 [--seconds 20] [--out FILE]

Builds the cell once, then runs one window per rate (the mix's
``arrivals.rps`` replaced) and prints, per rate: requests offered and
completed in the window, p50/p95 latency, the p95 of the first and of
the last fifth of arrivals, and how long after the window the last
answer came.  A backlog that grows shows as a last fifth far slower
than the first.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    import numpy as np
    from benchlib import drive, harness, traffic
    cell = harness.load_cell(args.workload, ROOT)
    harness.device_info(cell.chips)
    harness.enable_cache()
    sysm, _ = harness.prepare(cell, args.seed, args.seconds)
    lines = []
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = copy.deepcopy(cell.mix)
        mix["arrivals"]["rps"] = rate
        tr = traffic.build(mix, args.seed, args.seconds)
        run = drive.run(sysm, tr, args.seconds)
        res = harness.outcomes(sysm, run)
        recs = [r for r in run.records if r.done is not None]
        lat = np.array([r.done - r.due for r in recs]) * 1e3
        n5 = max(len(recs) // 5, 1)
        chip = [r for r in recs if sysm.on_chip
                and getattr(r.answer, "model", "") in sysm.on_chip]
        lc = np.array([r.done - r.due for r in chip]) * 1e3
        line = {"rate": rate, "offered": len(run.records),
                "failed": res["failed"],
                "done_in_window": res["decisions_per_s"] * args.seconds,
                "p50_ms": float(np.percentile(lat, 50)),
                "p95_ms": float(np.percentile(lat, 95)),
                "first_fifth_p95_ms": float(np.percentile(lat[:n5], 95)),
                "last_fifth_p95_ms": float(np.percentile(lat[-n5:], 95)),
                "tail_after_window_s": max(r.done for r in recs) - run.t1,
                "on_chip": len(chip),
                "on_chip_p95_ms": float(np.percentile(lc, 95))
                if len(lc) else None,
                "out_tokens_per_s": res["out_tokens_per_s"]}
        print(json.dumps(line), flush=True)
        lines.append(line)
    if args.out:
        with open(args.out, "a") as f:
            for line in lines:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
