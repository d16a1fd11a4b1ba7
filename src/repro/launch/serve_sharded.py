"""Sharded serving driver: diversified slates drawn from a candidate
set far larger than any single device would hold.

  PYTHONPATH=src python -m repro.launch.serve_sharded \
      --devices 8 --candidates 1000000 --dim 32 --slate 20 --window 8

On the CPU, forces ``--devices`` host devices via XLA_FLAGS — which
must happen before the first jax import, so this module keeps its
top-level imports jax-free (same contract as ``repro.launch.dryrun``);
on a TPU the flag shapes nothing and the mesh spans the process's
chips, one process for all of them.  Builds a ("data",) mesh over the
devices, synthesizes scores/features for M candidates,
and runs the full sharded pipeline end to end: sharded top-k shortlist
mask -> candidate-sharded greedy MAP (exact or sliding-window).  Each
device only ever holds a (D, M/P) column shard of the scaled feature
matrix plus its slice of the greedy state.

``--batch B`` serves a request batch of B users through the same mesh
in one ``Reranker.rerank`` call (per-user scores over shared features):
the candidate axis stays sharded and the per-step collectives batch
over B, so per-slate latency amortizes against the mesh instead of
paying B sequential round-trips.

``--stream N`` switches to **chunked slate emission**: the slate is
served through ``Reranker.stream`` in N-item chunks — the greedy state
stays sharded and device-resident between chunks, so the first chunk
ships after N greedy steps instead of after the whole slate.  The
report then carries ``first_chunk_s`` (time-to-first-chunk) next to
the whole-slate ``steady_call_s``, and ``--check`` verifies the
concatenated chunks equal the whole-slate slate index for index.
``--stream`` serves a single request (``--batch`` must stay 1).

``--check`` additionally runs the single-device ``rerank`` (vmapped
when ``--batch > 1``) on the same inputs and asserts the slates are
identical (the sharded path's bit-exactness guarantee); keep M modest
when checking.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8,
                    help="host (CPU) devices to create when JAX runs on the "
                         "CPU (0 = leave as-is); a TPU uses its own chips")
    ap.add_argument("--candidates", type=int, default=1_000_000)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--slate", type=int, default=20)
    ap.add_argument("--shortlist", type=int, default=0,
                    help="top-C shortlist mask (0 = rank the full candidate set)")
    ap.add_argument("--window", type=int, default=0,
                    help="sliding diversity window (0 = exact Algorithm 1)")
    ap.add_argument("--alpha", type=float, default=3.0)
    ap.add_argument("--batch", type=int, default=1,
                    help="request batch: B users' slates in one mesh call")
    ap.add_argument("--stream", type=int, default=0,
                    help="emit the slate in chunks of this size (0 = whole)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--check", action="store_true",
                    help="verify against the single-device rerank (small M only)")
    ap.add_argument("--metrics-out", default="")
    args = ap.parse_args(argv)

    if args.devices:
        from repro.launch.hostdev import force_host_device_flags

        # replace any inherited device-count flag so --devices always wins
        os.environ["XLA_FLAGS"] = force_host_device_flags(
            os.environ.get("XLA_FLAGS", ""), args.devices
        )

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType

    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving import DPPRerankConfig, Reranker, RerankRequest

    enable_compile_cache()

    if args.stream and args.batch > 1:
        raise SystemExit("--stream serves a single request; keep --batch 1")

    ndev = jax.device_count()
    mesh = jax.make_mesh((ndev,), ("data",), axis_types=(AxisType.Auto,))
    M, D, N, B = args.candidates, args.dim, args.slate, args.batch

    rng = np.random.default_rng(args.seed)
    feats = rng.normal(size=(M, D)).astype(np.float32)
    feats /= np.maximum(np.linalg.norm(feats, axis=1, keepdims=True), 1e-12)
    scores = rng.uniform(size=(B, M)).astype(np.float32)
    feats, scores = jnp.asarray(feats), jnp.asarray(scores)

    cfg = DPPRerankConfig(
        slate_size=N,
        shortlist=args.shortlist or M,
        alpha=args.alpha,
        eps=1e-6,
        window=args.window or None,
        mesh=mesh,
    )
    def serve(s, f, c):
        # one mesh call for the whole user batch; a single request drops
        # the batch axis so the (M,) fast path serves it
        req = RerankRequest(scores=s if B > 1 else s[0], feats=f)
        return Reranker(c).rerank(req)

    t0 = time.time()
    slate, dh = serve(scores, feats, cfg)
    slate.block_until_ready()
    t_first = time.time() - t0
    t0 = time.time()
    slate, dh = serve(scores, feats, cfg)
    slate.block_until_ready()
    t_steady = time.time() - t0

    stream_stats = None
    if args.stream:
        scfg = dataclasses.replace(cfg, chunk_size=args.stream)
        session = Reranker(scfg)
        sreq = RerankRequest(scores=scores[0], feats=feats)
        # warm pass compiles the chunk executors; timed pass measures
        # time-to-first-chunk and whole-stream wall clock
        for c, _ in session.stream(sreq):
            c.block_until_ready()
        t0 = time.time()
        chunks = []
        t_chunk1 = None
        for c, _ in session.stream(sreq):
            c.block_until_ready()
            if t_chunk1 is None:
                t_chunk1 = time.time() - t0
            chunks.append(np.asarray(c))
        t_stream = time.time() - t0
        stream_stats = {
            "chunk_size": args.stream,
            "first_chunk_s": round(t_chunk1, 3),
            "stream_total_s": round(t_stream, 3),
            "first_chunk_vs_whole": round(t_chunk1 / max(t_steady, 1e-9), 3),
        }
        if args.check:
            assert np.array_equal(
                np.concatenate(chunks), np.asarray(slate).reshape(-1)
            ), "streamed chunks diverged from the whole-slate slate"
            stream_stats["check"] = "ok (chunks concatenate to the slate)"

    slate_np = np.asarray(slate)
    n_sel = int((slate_np >= 0).sum())
    out = {
        "devices": ndev,
        "candidates": M,
        "per_device_candidates": -(-M // ndev),
        "dim": D,
        "slate": N,
        "batch": B,
        "window": args.window or None,
        "shortlist": args.shortlist or None,
        "n_selected": n_sel,
        "first_call_s": round(t_first, 3),
        "steady_call_s": round(t_steady, 3),
        "us_per_step": round(t_steady / max(N, 1) * 1e6, 1),
        "us_per_user_slate": round(t_steady / max(B, 1) * 1e6, 1),
    }
    if stream_stats is not None:
        out["stream"] = stream_stats

    if args.check:
        ref_cfg = DPPRerankConfig(
            slate_size=N, shortlist=args.shortlist or M, alpha=args.alpha,
            eps=1e-6, window=args.window or None,
        )
        ref, _ = serve(scores, feats, ref_cfg)
        assert np.array_equal(np.asarray(ref), slate_np), (
            "sharded slate diverged from the single-device path"
        )
        out["check"] = "ok (identical slate to single-device rerank)"

    print(json.dumps(out, indent=1))
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(out, f)
    return out


if __name__ == "__main__":
    main()
