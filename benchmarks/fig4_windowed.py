"""Windowed-vs-exact sweep (beyond-paper; NeurIPS'18 sliding window),
plus the past-the-VMEM-gate kernel sweep.

**N-sweep** — fixes the candidate set M and the window w, then grows
the slate length N up to 8x w.  The claim under test is the incremental
sliding-window implementation's complexity: per-step cost O(w M),
*independent of N* — the Cholesky ring ``C (w, M)`` is fixed-size
state, whereas the exact Algorithm 1 carries O(N M) state whose
per-step matvec grows with N.  Expected CSV shape: ``win_us_per_step``
flat in N (within noise; ``win_step_vs_N<w>`` stays ~1x).

**Gate sweep** — grows M through the resident kernels' VMEM budget.
Rows with ``past_gate=1`` are configs where
``untiled_vmem_bytes(D, M, w) > VMEM_BUDGET_BYTES``: before the tiled
kernels these silently degraded to the pure-jnp path; now the
``plan_tile`` auto-tiles the candidate axis (``tile_m`` in the derived
column) and the Pallas path keeps running.  Each row cross-checks the
kernel slate against the jnp oracle (``parity=ok``) and reports
``kernel_vs_jnp`` wall-clock (interpret mode on CPU measures structure,
not the TPU win).

  PYTHONPATH=src python -m benchmarks.fig4_windowed [--smoke | --full]
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax.numpy as jnp

from repro.core import (
    GreedySpec,
    greedy_map,
    map_relevance,
)


def setup(M, D, seed=0, alpha=2.0):
    rng = np.random.default_rng(seed)
    F = jnp.asarray(rng.normal(size=(D, M)), jnp.float32)
    F = F / jnp.maximum(jnp.linalg.norm(F, axis=0, keepdims=True), 1e-12)
    r = jnp.asarray(rng.uniform(size=M), jnp.float32)
    return F * map_relevance(r, alpha)[None, :]


def _time(fn, trials):
    fn().indices.block_until_ready()  # compile + warm
    best = float("inf")
    for _ in range(trials):
        t0 = time.perf_counter()
        fn().indices.block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def run(M=1000, D=100, w=8, trials=3):
    V = setup(M, D)
    rows = []
    for N in (w, 2 * w, 4 * w, 8 * w):
        win_spec = GreedySpec(k=N, window=w, eps=1e-6)
        exact_spec = GreedySpec(k=N, eps=1e-6)
        t_win = _time(lambda: greedy_map(win_spec, V=V), trials)
        t_exact = _time(lambda: greedy_map(exact_spec, V=V), trials)
        rows.append((N, w, t_win, t_exact))
    return rows


def run_gate(cells, k, trials):
    """cells: (M, D, w) triples; returns CSV-ready gate-sweep rows."""
    from repro.kernels.dpp_greedy import (
        VMEM_BUDGET_BYTES,
        dpp_greedy,
        plan_tile,
        untiled_vmem_bytes,
    )

    rows = []
    for M, D, w in cells:
        V = setup(M, D)[None]  # (1, D, M)
        past = int(untiled_vmem_bytes(D, M, w) > VMEM_BUDGET_BYTES)
        mode, tm = plan_tile(D, M, w, windowed=True)

        def timed(fn):
            sel, _ = fn()
            sel.block_until_ready()  # compile + warm
            best = float("inf")
            for _ in range(trials):
                t0 = time.perf_counter()
                fn()[0].block_until_ready()
                best = min(best, time.perf_counter() - t0)
            return best, sel

        t_k, sel_k = timed(
            lambda: dpp_greedy(V, k, window=w, eps=1e-6)
        )
        t_j, sel_j = timed(
            lambda: dpp_greedy(V, k, window=w, eps=1e-6, force_jnp=True)
        )
        parity = (
            "ok"
            if np.array_equal(np.asarray(sel_k), np.asarray(sel_j))
            else "FAIL"
        )
        rows.append(
            (M, D, w, k, past, mode, tm or 0, t_k, t_j, parity)
        )
    return rows


def main(fast_mode=False):
    M, D, w = (400, 48, 8) if fast_mode else (1000, 100, 8)
    trials = 2 if fast_mode else 5
    rows = run(M=M, D=D, w=w, trials=trials)
    print("name,us_per_call,derived")
    base = rows[0][2] / rows[0][0]
    for N, w, t_win, t_exact in rows:
        print(
            f"fig4_windowed_w{w}_N{N},{t_win*1e6:.1f},"
            f"win_us_per_step={t_win/N*1e6:.2f};"
            f"exact_us_per_step={t_exact/N*1e6:.2f};"
            f"win_step_vs_N{rows[0][0]}={t_win/N/base:.2f}x"
        )

    # gate sweep: one in-gate cell plus at least one past-the-gate cell
    # (the acceptance bar for the tiled kernels: the Pallas path keeps
    # running where the old vmem gate fell back to jnp); N > w so the
    # windowed kernel — eviction included — is what runs past the gate
    if fast_mode:
        cells, k, gtrials = [(4096, 32, 8), (65536, 64, 8)], 16, 1
    else:
        cells, k, gtrials = (
            [(4096, 32, 8), (65536, 64, 8), (131072, 64, 8)],
            16,
            3,
        )
    grows = run_gate(cells, k, gtrials)
    for M, D, w, k_, past, mode, tm, t_k, t_j, parity in grows:
        print(
            f"fig4_gate_M{M}_D{D}_w{w},{t_k*1e6:.1f},"
            f"past_gate={past};mode={mode};tile_m={tm};"
            f"jnp_us={t_j*1e6:.1f};kernel_vs_jnp={t_j/max(t_k, 1e-12):.2f}x;"
            f"parity={parity};N={k_}"
        )
    if any(r[9] != "ok" for r in grows):
        raise RuntimeError(f"fig4 gate sweep parity failure: {grows}")
    return rows, grows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, 1 timing trial (CI)")
    args = ap.parse_args()
    main(fast_mode=args.smoke or not args.full)
