#!/usr/bin/env python3
"""Chip smoke test: the served DPP reranking path, compiled on a TPU.

    python chip_smoke.py             # phases a-e on one chip
    python chip_smoke.py --chips 4   # a million-candidate pool on 4 chips

Every phase drives a public entry point of ``repro.serving.Reranker`` at
served widths, on data made from ``--seed``:

  a. ``rerank``: shortlist C=1000 of a 20,000-candidate pool, D=100,
     B=8 users in one call; exact (k=50) and windowed (w=8, k=100).
  b. ``rerank`` past the resident VMEM budget: pool = shortlist =
     131,072, D=64, w=8, k=50 — the tiled kernels.
  c. ``stream``: phase a's windowed request for user 0 in chunks of 10,
     through the fused chunk kernel; the chunks must equal phase a's
     slate.
  d. ``submit``: the continuous-batching router (8 slots, chunk 5,
     bucket 1000), 32 requests with k in [25, 50], every third masked;
     no jit cache miss after warm-up.
  e. ``session``: windowed w=8, two ``next_chunk(10)``, ``extend`` 200
     candidates, ``rescore`` 50, one more ``next_chunk(10)``.

``--chips 4`` runs instead the sharded path over a 4-chip "data" mesh
(1,048,576 candidates, D=64, w=8, k=50, tile_m=2048, B=4), the same
requests on one chip without a mesh, and user 0 against the reference.

Each slate is compared index for index with a float64 NumPy greedy
written below (independent of ``repro``): Algorithm 1, or its windowed
form rebuilt from the window at each step; the gains must agree to
rtol 2e-3.  The dispatch counters of ``repro.obs`` must show only
compiled kernel dispatches (no jnp path, nothing interpreted).  Earlier
lines report, per phase, the kernel modes, the compile seconds, the
wall time (a smoke reading, not a benchmark) and the device's
``peak_bytes_in_use``.  The last line is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Without a
TPU, or when any phase fails, the script exits non-zero and prints no
such line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
ALPHA = 3.0  # relevance trade-off, paper eq. 21: rel = ALPHA ** score
EPS = 1e-3
RTOL = 2e-3
# served widths (paper §5: a 1000-item shortlist at D=100, slates of 50)
POOL, DIM, SHORTLIST, K, WINDOW = 20_000, 100, 1000, 50, 8
USERS, CHUNK = 8, 10
BIG_M, BIG_DIM = 131_072, 64  # phase b: past the resident VMEM budget
ROUTER_REQUESTS, SLOTS, ROUTER_CHUNK = 32, 8, 5
EXTEND, RESCORE = 200, 50
MESH_M, MESH_USERS, MESH_TILE = 1 << 20, 4, 2048


class SmokeFailure(RuntimeError):
    """A phase produced a wrong slate or ran a path it should not."""


class served:
    """Adds the wall seconds of the enclosed serving calls (results
    materialized on the host) to ``ctx["served_s"]``."""

    def __init__(self, ctx):
        self.ctx = ctx

    def __enter__(self):
        self.t0 = time.perf_counter()

    def __exit__(self, *exc):
        self.ctx["served_s"] += time.perf_counter() - self.t0


# ---------------------------------------------------------------------------
# Data and the float64 reference (NumPy only)
# ---------------------------------------------------------------------------


def make_pool(rng, M, D):
    """Unit-norm item features (M, D) and the scorer's logits (M,)."""
    feats = rng.standard_normal((M, D), dtype=np.float32)
    feats /= np.linalg.norm(feats, axis=1, keepdims=True)
    scores = rng.standard_normal(M, dtype=np.float32)
    return feats, scores


def shortlist(scores, C, mask=None):
    """Global ids of the top-C selectable scores."""
    s = scores.astype(np.float64)
    if mask is not None:
        s = np.where(mask, s, -np.inf)
    return np.argsort(-s, kind="stable")[:C]


def kernel_columns(feats, scores):
    """The low-rank DPP kernel's columns, L = V^T V: V = (rel * f)^T."""
    rel = ALPHA ** scores.astype(np.float64)
    return (feats.astype(np.float64) * rel[:, None]).T


def ref_greedy(V, k, window=None, shown=(), dead=None):
    """Greedy MAP over the columns of ``V (D, n)`` in float64.

    Each step conditions every column on the last ``window`` shown
    columns (all of them when ``window`` is None) by a fresh Cholesky
    of their Gram matrix, so no incremental state is carried.  Returns
    ``(columns, gains)``; stops early where the best gain is <= EPS.
    """
    diag = np.einsum("dm,dm->m", V, V)
    shown = list(shown)
    dead = np.zeros(V.shape[1], bool) if dead is None else dead.copy()
    dead[shown] = True
    picks, gains = [], []
    for _ in range(k):
        win = shown if window is None else shown[-window:]
        d2 = diag.copy()
        if win:
            Vw = V[:, win]
            F = np.linalg.cholesky(Vw.T @ Vw)
            Ci = np.linalg.solve(F, Vw.T @ V)
            d2 -= np.einsum("wm,wm->m", Ci, Ci)
        d2[dead] = -np.inf
        j = int(np.argmax(d2))
        if not d2[j] > EPS * EPS:
            break
        picks.append(j)
        gains.append(np.sqrt(d2[j]))
        shown.append(j)
        dead[j] = True
    return np.asarray(picks, np.int64), np.asarray(gains)


def ref_rerank(feats, scores, C, k, window=None, mask=None):
    """The reference slate in global ids, -1 past an eps-stop."""
    ids = shortlist(scores, C, mask)
    cols, gains = ref_greedy(kernel_columns(feats[ids], scores[ids]), k,
                             window)
    slate = np.full(k, -1, np.int64)
    slate[: cols.size] = ids[cols]
    return slate, gains


def check_slate(what, ids, gains, ref_ids, ref_gains):
    ids, gains = np.asarray(ids).reshape(-1), np.asarray(gains).reshape(-1)
    if ids.shape != ref_ids.shape or not np.array_equal(ids, ref_ids):
        bad = np.flatnonzero(ids != ref_ids) if ids.shape == ref_ids.shape \
            else [0]
        raise SmokeFailure(
            f"{what}: slate differs from the float64 reference at "
            f"position {int(bad[0])} (got {ids[:12].tolist()}..., want "
            f"{ref_ids[:12].tolist()}...)"
        )
    n = ref_gains.size
    if not np.allclose(gains[:n], ref_gains, rtol=RTOL, atol=0.0):
        err = np.max(np.abs(gains[:n] - ref_gains) / np.abs(ref_gains))
        raise SmokeFailure(
            f"{what}: gains off the float64 reference by rtol {err:.2e}"
        )


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def phase_rerank(ctx):
    """a: whole slates for B=8 users, exact and windowed, resident."""
    from repro.serving import DPPRerankConfig, Reranker, RerankRequest

    feats, scores_b = ctx["feats"], ctx["scores_b"]
    out = {}
    for label, k, window in (("exact", K, None),
                             ("windowed", 2 * K, WINDOW)):
        rr = Reranker(DPPRerankConfig(
            slate_size=k, shortlist=SHORTLIST, alpha=ALPHA, eps=EPS,
            window=window, use_kernel=True,
        ))
        req = RerankRequest(scores=scores_b, feats=feats)
        with served(ctx):
            ids, dh = rr.rerank(req)
            ids, dh = np.asarray(ids), np.asarray(dh)
        for b in range(ids.shape[0]):
            ref, g = ref_rerank(np.asarray(feats), np.asarray(scores_b[b]),
                                SHORTLIST, k, window)
            check_slate(f"a/{label} user {b}", ids[b], dh[b], ref, g)
        out[label] = ids
    ctx["slates_a"] = out
    return f"B={ids.shape[0]} slates match"


def phase_tiled(ctx):
    """b: one pool past the resident budget; must dispatch tiled."""
    import jax.numpy as jnp

    from repro.serving import DPPRerankConfig, Reranker, RerankRequest

    rng = np.random.default_rng(ctx["seed"] + 1)
    M = BIG_M
    feats, scores = make_pool(rng, M, BIG_DIM)
    rr = Reranker(DPPRerankConfig(
        slate_size=K, shortlist=M, alpha=ALPHA, eps=EPS, window=WINDOW,
        use_kernel=True,
    ))
    req = RerankRequest(scores=jnp.asarray(scores), feats=jnp.asarray(feats))
    with served(ctx):
        ids, dh = map(np.asarray, rr.rerank(req))
    ref, g = ref_rerank(feats, scores, M, K, WINDOW)
    check_slate("b", ids, dh, ref, g)
    return f"M={M} slate matches"


def phase_stream(ctx):
    """c: phase a's windowed request for user 0, in chunks of 10."""
    from repro.serving import DPPRerankConfig, Reranker, RerankRequest

    rr = Reranker(DPPRerankConfig(
        slate_size=2 * K, shortlist=SHORTLIST, alpha=ALPHA, eps=EPS,
        window=WINDOW, use_kernel=True, chunk_size=CHUNK,
    ))
    req = RerankRequest(scores=ctx["scores_b"][0], feats=ctx["feats"])
    with served(ctx):
        chunks = [(np.asarray(i), np.asarray(d)) for i, d in rr.stream(req)]
    ids = np.concatenate([c[0] for c in chunks])
    dh = np.concatenate([c[1] for c in chunks])
    want = ctx["slates_a"]["windowed"][0]
    if not np.array_equal(ids, want):
        raise SmokeFailure("c: streamed chunks differ from phase a's slate")
    ref, g = ref_rerank(np.asarray(ctx["feats"]),
                        np.asarray(ctx["scores_b"][0]), SHORTLIST, 2 * K,
                        WINDOW)
    check_slate("c", ids, dh, ref, g)
    return f"{len(chunks)} chunks == phase a slate"


def phase_router(ctx):
    """d: 32 heterogeneous requests through the router; no re-jit."""
    import jax.numpy as jnp

    from repro import obs
    from repro.serving import (
        DPPRerankConfig, Reranker, RerankRequest, RouterConfig,
    )

    rng = np.random.default_rng(ctx["seed"] + 2)
    feats, pool = np.asarray(ctx["feats"]), ctx["feats"]
    M = feats.shape[0]
    reqs, specs = [], []
    for i in range(ROUTER_REQUESTS):
        scores = rng.standard_normal(M, dtype=np.float32)
        mask = None
        if i % 3 == 2:
            mask = np.ones(M, bool)
            mask[rng.choice(M, size=M // 4, replace=False)] = False
        k = int(rng.integers(K // 2, K + 1))
        specs.append((scores, mask, k))
        reqs.append(RerankRequest(
            scores=jnp.asarray(scores), feats=pool, slate_size=k,
            mask=None if mask is None else jnp.asarray(mask),
        ))
    rr = Reranker(
        DPPRerankConfig(slate_size=K, shortlist=SHORTLIST, alpha=ALPHA,
                        eps=EPS, use_kernel=True),
        router_config=RouterConfig(
            slots=SLOTS, chunk_size=ROUTER_CHUNK, max_candidates=SHORTLIST,
            max_queue=ROUTER_REQUESTS,
        ),
    )
    with served(ctx):
        warm = [rr.submit(r) for r in reqs[:SLOTS]]  # masked and unmasked
        rr.router.drain()
        cm = obs.compile_monitor()
        cm.mark()
        handles = warm + [rr.submit(r) for r in reqs[SLOTS:]]
        rr.router.drain()
    misses = int(cm.since_mark())
    for i, (h, (scores, mask, k)) in enumerate(zip(handles, specs)):
        ids, dh = h.result()
        ref, g = ref_rerank(feats, scores, SHORTLIST, k, None, mask)
        check_slate(f"d request {i}", ids, dh, ref, g)
    if misses != 0:
        raise SmokeFailure(
            f"d: jit_misses_after_warmup={misses}, expected 0"
        )
    return (f"{len(handles)} slates match; "
            f"jit_misses_after_warmup={misses}")


def phase_session(ctx):
    """e: a windowed session resumed across extend and rescore."""
    import jax.numpy as jnp

    from repro.serving import DPPRerankConfig, Reranker, RerankRequest

    rng = np.random.default_rng(ctx["seed"] + 3)
    feats = np.asarray(ctx["feats"])
    scores = np.asarray(ctx["scores_b"][1])
    w = WINDOW
    rr = Reranker(DPPRerankConfig(
        slate_size=2 * K, shortlist=SHORTLIST, alpha=ALPHA, eps=EPS,
        window=w, use_kernel=True, chunk_size=CHUNK,
    ))
    with served(ctx):
        sess = rr.session(RerankRequest(scores=ctx["scores_b"][1],
                                        feats=ctx["feats"]))
    # the reference pool: one float64 column per global id
    gids = list(shortlist(scores, SHORTLIST))
    cols = {g: kernel_columns(feats[g][None], scores[g][None])[:, 0]
            for g in gids}
    shown = []

    def pull(n, what):
        with served(ctx):
            ids, gains = sess.next_chunk(n)
        order = list(cols)
        V = np.stack([cols[g] for g in order], axis=1)
        pos = {g: c for c, g in enumerate(order)}
        picks, ref_g = ref_greedy(V, n, w, shown=[pos[g] for g in shown])
        check_slate(what, ids, gains, np.asarray(order)[picks], ref_g)
        shown.extend(int(g) for g in ids)

    pull(CHUNK, "e chunk 1")
    pull(CHUNK, "e chunk 2")
    new_f, new_s = make_pool(rng, EXTEND, feats.shape[1])
    with served(ctx):
        new_ids = sess.extend(jnp.asarray(new_s), jnp.asarray(new_f))
    for g, f, s in zip(new_ids, new_f, new_s):
        cols[int(g)] = kernel_columns(f[None], s[None])[:, 0]
    live = [g for g in gids if g not in set(shown)][:RESCORE]
    fresh = rng.standard_normal(RESCORE, dtype=np.float32)
    with served(ctx):
        sess.rescore(np.asarray(live), fresh)
    for g, s in zip(live, fresh):
        cols[g] = kernel_columns(feats[g][None], s[None])[:, 0]
    pull(CHUNK, "e chunk 3 (after extend + rescore)")
    return "3 chunks match the conditioned reference"


def phase_sharded(ctx):
    """--chips 4: B=4 users over 1,048,576 candidates on a 4-chip mesh,
    the same requests on one chip, user 0 against the reference."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType

    from repro.serving import DPPRerankConfig, Reranker, RerankRequest

    rng = np.random.default_rng(ctx["seed"] + 4)
    M, B = MESH_M, MESH_USERS
    feats, _ = make_pool(rng, M, BIG_DIM)
    scores = rng.standard_normal((B, M), dtype=np.float32)
    mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,),
                         devices=jax.devices()[:4])
    req = RerankRequest(scores=jnp.asarray(scores), feats=jnp.asarray(feats))
    common = dict(slate_size=K, shortlist=M, alpha=ALPHA, eps=EPS,
                  window=WINDOW, tile_m=MESH_TILE)
    with served(ctx):
        ids4, dh4 = map(np.asarray, Reranker(
            DPPRerankConfig(mesh=mesh, **common)
        ).rerank(req))
    with served(ctx):
        ids1 = np.asarray(Reranker(
            DPPRerankConfig(use_kernel=True, **common)
        ).rerank(req)[0])
    if not np.array_equal(ids4, ids1):
        raise SmokeFailure("sharded slates differ from the one-chip slates")
    ref, g = ref_rerank(feats, scores[0], M, K, WINDOW)
    check_slate("sharded user 0", ids4[0], dh4[0], ref, g)
    return f"B={B} sharded == one-chip; user 0 matches the reference"


PHASES = {
    1: (("a", phase_rerank, {"resident"}),
        ("b", phase_tiled, {"tiled"}),
        ("c", phase_stream, {"fused_chunk"}),
        ("d", phase_router, {"fused_chunk"}),
        ("e", phase_session, {"fused_chunk"})),
    4: (("sharded", phase_sharded, {"tiled"}),),
}


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


def dispatch_counts(registry):
    """``({mode: n}, interpreted n)`` from the obs dispatch counters."""
    counters = registry.snapshot()["counters"]
    modes = {}
    for key, n in counters.get("dpp_kernel_dispatch_total", {}).items():
        labels = dict(kv.split("=", 1) for kv in key.split(","))
        modes[labels["mode"]] = modes.get(labels["mode"], 0) + int(n)
    interpreted = int(sum(
        counters.get("dpp_kernel_interpreted_total", {}).values()
    ))
    return modes, interpreted


def make_context(seed):
    """The shared pool of phases a, c, d and e, and B users' scores."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    feats, _ = make_pool(rng, POOL, DIM)
    return {
        "seed": seed,
        "feats": jnp.asarray(feats),
        "scores_b": jnp.asarray(
            rng.standard_normal((USERS, POOL), dtype=np.float32)
        ),
    }


def run_phase(name, fn, expect, ctx, device):
    from repro import obs

    obs.disable()
    session = obs.enable(obs.ObsConfig(enabled=True))
    ctx["served_s"] = 0.0
    detail = fn(ctx)
    modes, interpreted = dispatch_counts(session.registry)
    compile_s = session.registry.counter("jit_compile_seconds_total").value()
    obs.disable()
    if "jnp" in modes or interpreted or not expect <= set(modes):
        raise SmokeFailure(
            f"phase {name}: dispatch modes {modes}, {interpreted} "
            f"interpreted; expected compiled {sorted(expect)} and no jnp"
        )
    peak = (device.memory_stats() or {}).get("peak_bytes_in_use")
    print(
        f"phase {name}: modes={modes} interpreted={interpreted} "
        f"compile_s={compile_s:.3f} served_wall_s={ctx['served_s']:.3f} "
        f"(smoke, not a benchmark; first calls, compiles included) "
        f"peak_bytes_in_use={peak} | {detail}",
        flush=True,
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    device = devices[0]
    if device.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{device.platform!r})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    print(f"device: {device.device_kind} x{len(devices)}; compile cache "
          f"{enable_compile_cache()}", flush=True)
    ctx = make_context(args.seed)
    try:
        for name, fn, expect in PHASES[args.chips]:
            run_phase(name, fn, expect, ctx, device)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
