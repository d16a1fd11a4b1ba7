#!/usr/bin/env python3
"""Readings of the control: the reference one precision step below the
configuration's float32 (``bench.reference.control_rerank``: every
product of V from bfloat16 operands, accumulated in float32), put in
the program's place and judged by the same comparison as the program.

    python bench/control.py --workload pool1m.rerank-share --seeds 1,2,3

For each seed it makes the cell's inputs as a run does (on the device,
from the seed, at the cell's own size), takes every distinct request of
the ring, and prints one JSON line: the control's ``pick_gap`` and
``gain_err`` over them, beside the cell's limits.  A limit is sound
where the control reads above it (PERF.md gives the readings).  The
benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(cell, seed):
    """``(pick_gap, gain_err, slates)`` of the control over every user of
    every request in the ring of ``cell`` made from ``seed``."""
    import numpy as np

    from bench import data, harness, reference, traffic

    cfg, mix = cell.cfg, cell.mix
    M = cfg["pool"]
    C = harness.shortlist_width(cfg, M)
    B, R = mix["users_per_call"], mix["ring"]
    rng = np.random.default_rng(seed)
    ks = traffic.slate_sizes(cfg, mix, R, rng)
    masked = traffic.masked_entries(cfg, mix, R)
    feats, scores, masks = data.make_inputs(
        seed, M, cfg["dim"], B, R, n_masked=len(masked),
        mask_share=cfg.get("mask_share", 0.0), score_dist=cfg["score_dist"])
    feats = np.asarray(feats)
    gap = err = 0.0
    n = 0
    for r in range(R):
        mask = np.asarray(masks[masked.index(r)]) if r in masked else None
        s_r = np.asarray(scores[r]).reshape(-1, M)
        for u in range(B):
            k = int(ks[r])
            ids, gains = reference.control_rerank(
                feats, s_r[u], C, k, cfg["alpha"], cfg["eps"],
                cfg.get("window"), mask)
            f = reference.follow(feats, s_r[u], C, ids, gains, cfg["alpha"],
                                 cfg["eps"], cfg.get("window"), mask)
            if f.invalid is not None:
                gap = err = float("inf")
            else:
                gap, err = max(gap, f.pick_gap), max(err, f.gain_err)
            n += 1
    return gap, err, n


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path.insert(0, ROOT)
    from bench import harness

    cell = harness.load_cell(args.workload, rehearse=args.rehearse)
    harness.enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        gap, err, n = readings(cell, seed)
        print(json.dumps({
            "workload": args.workload, "seed": seed, "slates": n,
            "pick_gap": gap, "pick_gap_limit": cell.limits["pick_gap"],
            "gain_err": err, "gain_err_limit": cell.limits["gain_err"],
            "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
