"""Continuous-batching serving driver: live heterogeneous requests
through ``RerankRouter`` behind a CTR scorer.

  PYTHONPATH=src python -m repro.launch.serve_router --arch deepfm \
      --requests 24 --candidates 2000 --slots 4 --chunk 4 --qps 50

A synthetic open-loop client offers one request every ``1/qps`` seconds:
each request is one user scored against the shared candidate pool by
the recsys model (as in ``repro.launch.serve``), with a per-request
slate length drawn from ``[slate/2, slate]``, an already-seen mask for
every third user, and an optional per-request ``--deadline``.  Requests
are submitted to one ``Reranker.submit`` session; the driver pumps the
router, measuring completion latency percentiles, time-to-first-chunk,
sustained QPS and the batch fill ratio, and cross-checks a sample of
completed slates index-for-index against per-request ``rerank``.

``--trace-out trace.json`` writes every span of the run (the
``router.pump`` decomposition among them) as Chrome ``trace_event``
JSON — load it in https://ui.perfetto.dev.  ``--metrics-out`` then also
embeds the metrics snapshot (kernel dispatch counts, marginal
evaluations, jit cache misses) next to the driver numbers; the
``jit_misses_after_warmup`` field is the structural no-re-jit check —
0 means the measured loop ran entirely on cached computations.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs import get_arch
from repro.models import recsys as recsys_mod
from repro.data import recsys_batches
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import (
    DPPRerankConfig,
    ObsConfig,
    Reranker,
    RerankRequest,
    RouterConfig,
)
from repro.serving.router import RouterQueueFull


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepfm")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="the arch's reduced config (--no-reduced: full size)")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--candidates", type=int, default=2000)
    ap.add_argument("--slate", type=int, default=16)
    ap.add_argument("--shortlist", type=int, default=200)
    ap.add_argument("--alpha", type=float, default=3.0)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--chunk", type=int, default=4)
    ap.add_argument("--qps", type=float, default=50.0)
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="per-request latency budget in seconds (0 = none)")
    ap.add_argument("--use-kernel", action="store_true")
    ap.add_argument("--parity-sample", type=int, default=4)
    ap.add_argument("--metrics-out", default="")
    ap.add_argument("--trace-out", default="",
                    help="write the run's spans as Chrome trace_event JSON "
                         "(Perfetto-loadable)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    # observability is threaded through the serving configs, not turned
    # on globally here — the run exercises the same wiring users get
    ocfg = (
        ObsConfig(enabled=True)
        if (args.metrics_out or args.trace_out) else None
    )

    spec = get_arch(args.arch)
    assert spec.family == "recsys", "serving driver targets the recsys family"
    cfg = spec.reduced() if args.reduced else spec.config
    params = recsys_mod.init_params(jax.random.PRNGKey(0), cfg)
    Mc = min(args.candidates, cfg.vocab_sizes[cfg.item_field])
    shortlist = min(args.shortlist, Mc)

    rcfg = DPPRerankConfig(
        slate_size=args.slate, shortlist=shortlist, alpha=args.alpha,
        use_kernel=args.use_kernel, chunk_size=args.chunk,
    )
    rr = Reranker(rcfg, router_config=RouterConfig(
        slots=args.slots, chunk_size=args.chunk, max_queue=args.requests,
        max_candidates=shortlist, obs=ocfg,
    ))

    # score every user against the shared candidate pool up front — the
    # scorer is not what this driver measures
    cand = jnp.arange(Mc, dtype=jnp.int32)
    gen = recsys_batches(cfg.vocab_sizes, args.requests, seed=1)
    user = jnp.asarray(next(gen)["ids"])

    @jax.jit
    def score_all(params, user_ids):
        def score_one(u):
            ids = jnp.broadcast_to(u[None], (Mc,) + u.shape).astype(jnp.int32)
            ids = jnp.concatenate(
                [ids[:, : cfg.item_field],
                 cand[:, None, None] if u.shape[-1] == 1 else
                 jnp.concatenate([cand[:, None],
                                  jnp.full((Mc, u.shape[-1] - 1), -1,
                                           jnp.int32)], axis=1)[:, None],
                 ids[:, cfg.item_field + 1:]],
                axis=1,
            )
            return recsys_mod.serve_scores(params, ids, cfg)

        return jax.vmap(score_one)(user_ids)

    scores = jax.block_until_ready(score_all(params, user))  # (B, Mc)
    feats = recsys_mod.item_embeddings(params, cand, cfg)  # (Mc, D)

    rng = np.random.default_rng(0)
    reqs = []
    for b in range(args.requests):
        mask = None
        if b % 3 == 2:
            m = np.ones(Mc, bool)
            m[rng.choice(Mc, size=Mc // 5, replace=False)] = False
            mask = jnp.asarray(m)
        reqs.append(RerankRequest(
            scores=scores[b], feats=feats,
            slate_size=int(rng.integers(max(args.slate // 2, 1),
                                        args.slate + 1)),
            mask=mask,
            deadline=args.deadline or None,
            rid=b,
        ))

    # warm the slot geometry's compile out of the measurement; the warm
    # set must cover the masked-admission program too (mask presence is
    # a host-side branch — a distinct one-time compile the miss counter
    # would otherwise report when the first masked request lands
    # mid-measurement)
    warm_reqs = list(reqs[: args.slots])
    if warm_reqs and not any(r.mask is not None for r in warm_reqs):
        masked = next((r for r in reqs if r.mask is not None), None)
        if masked is not None:
            warm_reqs[-1] = masked
    warm = [rr.submit(r) for r in warm_reqs]
    rr.router.drain()
    rr = Reranker(rcfg, router_config=RouterConfig(
        slots=args.slots, chunk_size=args.chunk, max_queue=args.requests,
        max_candidates=shortlist, obs=ocfg,
    ))
    cm = obs.compile_monitor()
    if cm is not None:
        cm.mark()  # every compile past here is a measured-loop re-jit

    gap = 1.0 / args.qps
    t0 = time.perf_counter()
    handles, arrived, done_at = [], {}, {}
    pending = list(reqs)
    offered = 0
    while pending or any(not h.done for h in handles):
        now = time.perf_counter() - t0
        while pending and offered * gap <= now:
            try:
                h = rr.submit(pending[0])
            except RouterQueueFull:
                break
            arrived[id(h)] = now
            handles.append(h)
            pending.pop(0)
            offered += 1
        rr.router.pump()
        now = time.perf_counter() - t0
        for h in handles:
            if h.done and id(h) not in done_at:
                done_at[id(h)] = now
    makespan = max(done_at.values())

    lat = np.array([done_at[id(h)] - arrived[id(h)] for h in handles])
    ttfc = np.array([h.ttfc for h in handles if h.ttfc is not None])
    # read the miss counter BEFORE the parity sample: per-request rerank
    # below legitimately compiles one whole-slate program per distinct k
    misses_after_warmup = int(cm.since_mark()) if cm is not None else None
    parity_ok = True
    for h, req in list(zip(handles, reqs))[: args.parity_sample]:
        if h.timed_out:
            continue
        ei, _ = rr.rerank(req)
        parity_ok &= bool(np.array_equal(h.slate()[0], np.asarray(ei)))
    st = rr.router.stats
    out = {
        "arch": args.arch,
        "requests": len(handles),
        "candidates": Mc,
        "slots": args.slots,
        "chunk": args.chunk,
        "offered_qps": args.qps,
        "sustained_qps": round(len(handles) / makespan, 1),
        "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 2),
        "p95_ms": round(float(np.percentile(lat, 95)) * 1e3, 2),
        "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 2),
        "mean_ttfc_ms": round(float(ttfc.mean()) * 1e3, 2),
        "fill_ratio": round(st.fill_ratio, 3),
        "completed": st.completed,
        "timed_out": st.timed_out,
        "eps_stopped": st.eps_stopped,
        "parity_sample_ok": parity_ok,
    }
    if misses_after_warmup is not None:
        out["jit_misses_after_warmup"] = misses_after_warmup
    print(json.dumps(out, indent=1))
    if obs.registry() is not None:
        out["obs"] = obs.registry().snapshot()
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(out, f)
    if args.trace_out and obs.tracer() is not None:
        obs.tracer().write_chrome(args.trace_out)
        print(f"trace: {args.trace_out} ({obs.tracer().total} spans)")
    if not parity_ok:
        raise SystemExit("router slates diverged from per-request rerank")
    return out


if __name__ == "__main__":
    main()
