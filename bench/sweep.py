#!/usr/bin/env python3
"""Find the knee of an open-loop router cell: the highest offered rate
the router sustains without a growing backlog.

    python bench/sweep.py --workload feed1k.router-steady --seed 1 \
        --seconds 8 --rates 50,100,200,400

One process: build and warm the cell once, then offer each rate for
``--seconds`` (Poisson arrivals from the mix's generator), wait for the
window's requests to finish, and print one JSON line per rate: the
requests due, failed (refused or unfinished), the p50 and p95 of time
to first chunk and to the whole slate from the due time, the sender's
lateness, and the backlog (requests sent and not finished) at the
window's middle and at its end.  The backlog grows where the end's is
well above the middle's.  Slates are not checked here; ``run.py`` is.
The rate the cell runs at is written into the mix's file by hand.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def backlog(recs, t):
    return sum(1 for r in recs if r.sent is not None and r.sent <= t
               and (r.done is None or r.done > t))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path.insert(0, ROOT)
    import numpy as np

    from bench import harness, traffic

    cell = harness.load_cell(args.workload, rehearse=args.rehearse)
    harness.enable_compile_cache()
    harness.import_program()
    if not args.rehearse:
        harness.check_devices(cell)
    s = harness.build(cell, args.seed)
    harness.warm(s, cell.mix)
    for n, rate in enumerate(float(r) for r in args.rates.split(",")):
        rng = np.random.default_rng([args.seed, 2, n])
        due = traffic.poisson_due(rate, args.seconds, rng,
                                  cell.mix.get("burst", 1))
        recs, t_end = traffic.open_loop(
            s.rr.router, s.rr.submit, s.ring, due, args.seconds,
            harness.no_probe, harness.WAIT_AFTER)
        out = harness.open_metrics(recs, t_end, args.seconds)
        ttfc = [r.first - r.due for r in recs if r.done is not None]
        slate = [r.done - r.due for r in recs if r.done is not None]
        late = [r.sent - r.due for r in recs]
        print(json.dumps({
            "rate_per_s": rate, "due": len(recs),
            "failed": out["failed"],
            "ttfc_p50_ms": 1e3 * harness.percentile(ttfc, 50),
            "ttfc_p95_ms": out["metrics"]["ttfc_p95_ms"],
            "slate_p50_ms": 1e3 * harness.percentile(slate, 50),
            "slate_p95_ms": out["metrics"]["slate_p95_ms"],
            "late_p95_ms": 1e3 * harness.percentile(late, 95),
            "backlog_mid": backlog(recs, args.seconds / 2),
            "backlog_end": backlog(recs, args.seconds),
            "drain_s": t_end - args.seconds,
        }), flush=True)
        time.sleep(0.5)
    return 0


if __name__ == "__main__":
    sys.exit(main())
