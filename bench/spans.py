"""Run one cell as ``run.py --trace 1`` does, and also read the program's
own spans.

  python bench/spans.py --workload <cell> --seed <n> --seconds <s>

``trace.reduce_profile`` keeps only the benchmark's ``bench.*`` host
spans, so the metrics that read the program's ``repro.*`` spans
(``PROGRAM_METRICS``, readers under ``bench/metrics/``) are not in
``BENCHMARK.json``.  This script reduces the traced run with
``program_spans.load``, which keeps those spans too, and reads the
cell's per-layer metrics and these in the same run.  The last line of
standard output is the result object ``run.py`` prints.
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

PROGRAM_METRICS = [("queue_wait_ms", "ms"), ("catalog_lookup_ms", "ms"),
                   ("encode_ms", "ms"), ("idle_host_share.decide", "%")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    from benchlib import harness, peaks, program_spans, trace
    try:
        cell = harness.load_cell(args.workload, ROOT)
        harness.device_info(cell.chips)
    except (harness.NoChip, peaks.UnknownDevice, KeyError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    trace.load = program_spans.load
    cell.per_layer += [{"name": n, "unit": u} for n, u in PROGRAM_METRICS]
    result = harness.run_cell(cell, args.seed % (1 << 62), args.seconds,
                              True, t_process=T_PROCESS)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
