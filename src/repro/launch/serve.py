"""Batched serving driver with DPP slate diversification.

  PYTHONPATH=src python -m repro.launch.serve --arch deepfm \
      --requests 32 --candidates 2000 --slate 10 --alpha 3.0

``--no-reduced`` serves the arch's full-size config.

Serving pipeline per request batch (the paper's §5 scenario end-to-end):
  1. score all candidates with the CTR model (batched forward);
  2. shortlist top-C;
  3. Div-DPP (Algorithm 1) re-ranks the shortlist into a diverse slate.

Reports throughput and slate diversity metrics (average/min/median
dissimilarity — the paper's metrics) vs a pure Top-N baseline.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.core import mean_slate_diversity, top_n_select
from repro.data import recsys_batches
from repro.launch.compile_cache import enable_compile_cache
from repro.models import recsys as recsys_mod
from repro.serving import DPPRerankConfig, Reranker, RerankRequest


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepfm")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="the arch's reduced config (--no-reduced: full size)")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--candidates", type=int, default=2000)
    ap.add_argument("--slate", type=int, default=10)
    ap.add_argument("--shortlist", type=int, default=200)
    ap.add_argument("--alpha", type=float, default=3.0)
    ap.add_argument("--use-kernel", action="store_true")
    ap.add_argument("--metrics-out", default="")
    args = ap.parse_args(argv)
    enable_compile_cache()

    spec = get_arch(args.arch)
    assert spec.family == "recsys", "serving driver targets the recsys family"
    cfg = spec.reduced() if args.reduced else spec.config
    params = recsys_mod.init_params(jax.random.PRNGKey(0), cfg)
    Mc = min(args.candidates, cfg.vocab_sizes[cfg.item_field])
    B = args.requests
    rr = Reranker(DPPRerankConfig(
        slate_size=args.slate, shortlist=min(args.shortlist, Mc),
        alpha=args.alpha, use_kernel=args.use_kernel,
    ))

    # candidate item ids are shared; user contexts vary per request
    cand = jnp.arange(Mc, dtype=jnp.int32)
    gen = recsys_batches(cfg.vocab_sizes, B, seed=1)
    user = jnp.asarray(next(gen)["ids"])  # (B, F, H)

    @jax.jit
    def serve(params, user_ids):
        def score_one(u):
            ids = jnp.broadcast_to(u[None], (Mc,) + u.shape).astype(jnp.int32)
            ids = jnp.concatenate(
                [ids[:, : cfg.item_field],
                 cand[:, None, None] if u.shape[-1] == 1 else
                 jnp.concatenate([cand[:, None],
                                  jnp.full((Mc, u.shape[-1] - 1), -1, jnp.int32)],
                                 axis=1)[:, None],
                 ids[:, cfg.item_field + 1:]],
                axis=1,
            )
            return recsys_mod.serve_scores(params, ids, cfg)

        scores = jax.vmap(score_one)(user_ids)  # (B, Mc)
        feats = recsys_mod.item_embeddings(params, cand, cfg)  # (Mc, D)
        slates, dh = rr.rerank(RerankRequest(scores=scores, feats=feats))
        return scores, slates

    t0 = time.time()
    scores, slates = jax.block_until_ready(serve(params, user))
    t_first = time.time() - t0
    t0 = time.time()
    scores, slates = jax.block_until_ready(serve(params, user))
    t_steady = time.time() - t0

    feats = np.asarray(recsys_mod.item_embeddings(params, cand, cfg))
    S = feats @ feats.T
    slates_np = np.asarray(slates)
    top_slates = np.stack(
        [top_n_select(np.asarray(scores[b]), args.slate) for b in range(B)]
    )
    div_dpp = mean_slate_diversity(slates_np, S)
    div_top = mean_slate_diversity(top_slates, S)
    out = {
        "arch": args.arch,
        "requests": B,
        "candidates": Mc,
        "first_batch_s": round(t_first, 3),
        "steady_batch_s": round(t_steady, 3),
        "req_per_s": round(B / t_steady, 1),
        "diversity_dpp": div_dpp,
        "diversity_top": div_top,
        "mean_rel_dpp": float(np.take_along_axis(np.asarray(scores), slates_np, 1).mean()),
        "mean_rel_top": float(np.take_along_axis(np.asarray(scores), top_slates, 1).mean()),
    }
    print(json.dumps(out, indent=1))
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(out, f)
    return out


if __name__ == "__main__":
    main()
