#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python bench/run.py --workload CELL --seed N --seconds S --trace 0|1

Builds the cell's inputs from ``--seed`` on the device, warms the
shapes the cell uses, measures for ``--seconds``, checks every slate
the window served against the float64 reference, and prints one JSON
object as the last line of standard output:

    {"correct", "attempted", "failed", "metrics", "device",
     ["breakdown",] "checks"}

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a profile of a few seconds of the window.
Each number compared for ``correct`` is printed beside its limit, on
the last lines of standard error and under ``checks``.

Without a TPU, or with fewer chips than the cell asks for, the run
exits with code 2 and prints no result.  ``--rehearse`` runs the cell
at a tiny size on whatever JAX finds (the CPU here), prints its result
line after ``rehearsal:`` and exits with code 3: it is a check of the
control flow, never a measurement.

JAX's persistent compilation cache is kept at ``<checkout>/.jax_cache``.
"""
import time

T_START = time.perf_counter()  # set-up runs from here to the window

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any platform; exits 3")
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="copy the profile (.xplane.pb) of a --trace 1 run "
                         "into DIR")
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path.insert(0, ROOT)
    from bench import harness

    cell = harness.load_cell(args.workload, rehearse=args.rehearse)
    harness.enable_compile_cache()
    try:
        line = harness.run(cell, args.seed, args.seconds, args.trace,
                           T_START, rehearse=args.rehearse,
                           keep_trace=args.keep_trace)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    text = json.dumps(line)
    if args.rehearse:
        print("rehearsal: " + text)
        return 3
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
