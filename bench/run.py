"""Run one benchmark cell once and print its result line.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are named in
``BENCHMARK.json`` at the root of the checkout.  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks`` (each number compared and its
limit; they are also the last lines of standard error).  Earlier lines
report compilations inside the window, how late the load generator
ran and the share of requests served on the chip.

Exits non-zero with no result line when JAX sees no TPU, fewer chips
than the cell asks for, a device that the peak table lacks, or a
backend architecture with no module under ``bench/backends/``.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    # JAX's persistent compilation cache stays inside this checkout, at a
    # fixed path, whatever the environment names: two checkouts on one
    # machine share nothing
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    from benchlib import backend, harness, peaks
    try:
        cell = harness.load_cell(args.workload, ROOT)
        harness.device_info(cell.chips)
    except (harness.NoChip, peaks.UnknownDevice, backend.UnknownArchitecture,
            KeyError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    # any whole number is a seed; negative ones fold into the same range
    seed = args.seed % (1 << 62)
    result = harness.run_cell(cell, seed, args.seconds,
                              bool(args.trace), t_process=T_PROCESS)
    print(json.dumps(result), flush=True)
    # the check lines stay the last lines of standard error: whatever the
    # runtime logs while shutting down goes nowhere
    sys.stderr.flush()
    os.dup2(os.open(os.devnull, os.O_WRONLY), 2)
    return 0


if __name__ == "__main__":
    sys.exit(main())
