"""Readings that the limits of ``correct`` are set from.

  python bench/calibrate.py --workload <cell> --seeds 1,2,... \
      [--control-seeds 1,2,3] [--seconds 10] [--out FILE]

For each seed, in one process: build the cell from the seed, warm up
(the first seed only: the later ones find every shape compiled), run a
short window at the cell's own load, and read every number that
``correct`` compares for the program.  For the control seeds, read the
same numbers for the control as well, through the same comparison that
decides ``correct`` (``check.check_run(control=True)``): the reference
put in the program's place one step below the configured precision
(analyzer with fp8 weights and bf16 activations, routing at ``high``
precision, the backend with fp8 weights and bf16 activations), at the
same sample of requests.  One JSON line per
seed goes to standard output and to ``--out``.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def _ints(s):
    return [int(x) for x in s.split(",") if x]


def readings(cell, seed, seconds, control, cache_dir=None, warm=True):
    from benchlib import check, drive, harness
    sysm, tr = harness.prepare(cell, seed, seconds, cache_dir, warm)
    run = drive.run(sysm, tr, seconds)
    res = harness.outcomes(sysm, run)
    out = {"seed": seed, "attempted": res["attempted"],
           "failed": res["failed"],
           "on_chip_requests": res["on_chip_requests"]}
    sides = [("program", False)] + ([("control", True)] if control else [])
    for side, ctl in sides:
        checks = check.check_run(sysm, run, seed, control=ctl)
        out[side] = {k: v["value"] for k, v in checks.items()}
        out[side + "_correct"] = check.verdict(checks, res["failed"])
    del sysm, tr, run
    gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, required=True)
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    from benchlib import harness
    cell = harness.load_cell(args.workload, ROOT)
    harness.device_info(cell.chips)
    harness.enable_cache()
    sink = open(args.out, "a") if args.out else None
    try:
        for i, seed in enumerate(args.seeds):
            t = time.perf_counter()
            # the first seed warms every shape up; the later ones reuse
            # them (their windows are not timed)
            line = readings(cell, seed, args.seconds,
                            seed in args.control_seeds, warm=i == 0)
            line["wall_s"] = time.perf_counter() - t
            text = json.dumps(line)
            print(text, flush=True)
            if sink:
                sink.write(text + "\n")
                sink.flush()
    finally:
        if sink:
            sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
