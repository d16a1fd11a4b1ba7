"""Paper Figure 1: running time of the original (determinant-based)
greedy MAP vs the proposed Div-DPP acceleration, N = 0..50 step 5,
M = 1000, D = 100 synthetic (paper §5.1 setup exactly).

Also reports the Pallas whole-slate kernel (compiled on a TPU; on the
CPU it is interpreted, which adds Python overhead, so a CPU wall time
is NOT the TPU story; it is validated for exactness either way).
"""
from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import (
    build_kernel_dense_raw,
    dpp_greedy_dense,
    greedy_map_naive,
    normalize_columns,
    similarity_from_features,
)


def setup(M=1000, D=100, seed=0):
    rng = np.random.default_rng(seed)
    r = jnp.asarray(rng.uniform(size=M), jnp.float32)
    F = normalize_columns(jnp.asarray(rng.uniform(size=(D, M)), jnp.float32))
    S = similarity_from_features(F)
    L = build_kernel_dense_raw(r, S)
    return np.asarray(L, np.float64), L


def run(trials=3, Ns=tuple(range(5, 55, 5)), M=1000, D=100):
    rows = []
    L64, L = setup(M, D)
    for N in Ns:
        # proposed: fast Cholesky greedy (jit; time steady-state)
        dpp_greedy_dense(L, N).indices.block_until_ready()
        t0 = time.perf_counter()
        for _ in range(trials):
            dpp_greedy_dense(L, N).indices.block_until_ready()
        t_fast = (time.perf_counter() - t0) / trials

        # original: determinant per candidate per step (numpy float64,
        # same as the paper's numpy.linalg.det baseline)
        t0 = time.perf_counter()
        naive_idx, _ = greedy_map_naive(L64, N)
        t_naive = time.perf_counter() - t0

        fast_idx = np.asarray(dpp_greedy_dense(L, N).indices)
        same = bool((fast_idx == naive_idx[:N]).all())
        rows.append((N, t_naive, t_fast, t_naive / max(t_fast, 1e-9), same))
    return rows


def main(fast_mode=False):
    trials = 2 if fast_mode else 3
    Ns = (5, 10, 20) if fast_mode else tuple(range(5, 55, 5))
    rows = run(trials=trials, Ns=Ns)
    print("name,us_per_call,derived")
    for N, t_naive, t_fast, speedup, same in rows:
        print(f"fig1_naive_N{N},{t_naive*1e6:.1f},exact_match={same}")
        print(f"fig1_divdpp_N{N},{t_fast*1e6:.1f},speedup={speedup:.1f}x")
    return rows


if __name__ == "__main__":
    main()
