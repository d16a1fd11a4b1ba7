"""Sharded candidate-axis greedy MAP: weak-scaling sweep (beyond-paper).

Fixes the per-device shard size M/P and grows the candidate set M with
the device count P.  The claim under test is the sharded subsystem's
per-step structure: O(w M / P) local work plus one tiny
argmax-allreduce and one winner-broadcast — so ``us_per_user_step``
stays roughly flat as M grows with M/P fixed.  Each (mode, P) cell also
gets a B>1 row: a user batch sharing the mesh (loop state (B, Mloc) per
device, collectives batched over B), whose per-user cost should sit
well below B x the single-slate row.  (On a host-device CPU mesh the
"devices" share the same cores, so flatness is approximate there; the
CSV is evidence of the scaling structure, a real multi-chip mesh is
where the wall-clock win lands.)

On the CPU, XLA pins the host device count at first init, so each P
runs in a fresh subprocess (same pattern as tests/test_distributed.py);
the parent collects and prints one CSV row per (mode, P).  On a TPU the
process that holds the chips runs the sweep itself, over all of
``jax.devices()`` (a child could not reach a chip its parent holds).

  PYTHONPATH=src python -m benchmarks.fig5_sharded [--full | --smoke]
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from repro.launch.hostdev import force_host_device_flags  # jax-import-free


def _inner(args) -> list:
    """One P: runs inside the subprocess with the device count already
    forced (CPU), or in-process over the chips (TPU).  Prints and
    returns the CSV rows."""
    import time

    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType

    from repro.core.sharded import dpp_greedy_sharded
    from repro.kernels.dpp_greedy import VMEM_BUDGET_BYTES, untiled_vmem_bytes

    P = jax.device_count()
    M = args.mloc * P
    mesh = jax.make_mesh((P,), ("data",), axis_types=(AxisType.Auto,))
    rng = np.random.default_rng(0)
    Vb = jnp.asarray(
        rng.normal(size=(args.batch, args.dim, M)), jnp.float32
    ) / np.sqrt(args.dim)

    rows = []
    # B=1 single-slate rows plus a B>1 batched row per mode: the batched
    # rows measure the users x candidates composition — B slates share
    # the mesh, per-step collectives batch over B, so us_per_user_step
    # should sit well below B x the single-slate cost.  Each cell also
    # gets a tile_m row (tm<tile> label): the per-device local update
    # streamed through the tiled Pallas pass — past_gate=1 marks shards
    # whose (D, Mloc) working set exceeds the resident kernels' VMEM
    # budget, i.e. the regime the old vmem gate surrendered to jnp.
    for label, window in (("exact", None), (f"w{args.window}", args.window)):
        state_rows = args.slate if window is None else min(window, args.slate)
        past = int(
            untiled_vmem_bytes(args.dim, args.mloc, state_rows)
            > VMEM_BUDGET_BYTES
        )
        for tile in (None, args.tile_m):
            for B in sorted({1, args.batch}):
                V = Vb[0] if B == 1 else Vb[:B]
                fn = lambda: dpp_greedy_sharded(
                    V, args.slate, mesh=mesh, window=window, eps=1e-6,
                    tile_m=tile,
                )
                fn().indices.block_until_ready()  # compile + warm
                best = float("inf")
                for _ in range(args.trials):
                    t0 = time.perf_counter()
                    fn().indices.block_until_ready()
                    best = min(best, time.perf_counter() - t0)
                tl = "" if tile is None else f"_tm{tile}"
                rows.append(
                    f"fig5_sharded_{label}{tl}_B{B}_P{P}_M{M},{best*1e6:.1f},"
                    f"us_per_user_step={best/(args.slate*B)*1e6:.2f};"
                    f"B={B};Mloc={args.mloc};D={args.dim};N={args.slate};"
                    f"tile_m={tile or 0};past_gate={past}"
                )
                print(rows[-1])
    return rows


def run(devices, mloc, dim, slate, window, trials, batch, tile_m):
    import jax

    if jax.default_backend() != "cpu":
        return _inner(argparse.Namespace(
            mloc=mloc, dim=dim, slate=slate, window=window, trials=trials,
            batch=batch, tile_m=tile_m,
        ))
    rows, failures = [], []
    for P in devices:
        env = dict(os.environ)
        # preserve inherited XLA flags, replacing only the device count
        env["XLA_FLAGS"] = force_host_device_flags(env.get("XLA_FLAGS", ""), P)
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (os.path.join(REPO, "src"), env.get("PYTHONPATH", "")) if p
        )
        cmd = [
            sys.executable, "-m", "benchmarks.fig5_sharded", "--inner",
            "--mloc", str(mloc), "--dim", str(dim), "--slate", str(slate),
            "--window", str(window), "--trials", str(trials),
            "--batch", str(batch), "--tile-m", str(tile_m),
        ]
        out = subprocess.run(
            cmd, capture_output=True, text=True, env=env, cwd=REPO, timeout=1200
        )
        if out.returncode != 0:
            tail = out.stderr.strip().splitlines()[-1:] or ["<no stderr>"]
            print(f"fig5_sharded_P{P},0,error={tail[0]}")
            failures.append((P, tail[0]))
            continue
        for line in out.stdout.strip().splitlines():
            if line.startswith("fig5_sharded"):
                print(line)
                rows.append(line)
    if failures:
        # fail loudly so the CI smoke step (and benchmarks.run) go red
        raise RuntimeError(f"fig5_sharded subprocess failures: {failures}")
    return rows


_PRESETS = {
    # fast: tiny shapes + 1/2 devices (CI smoke / benchmarks.run default)
    True: dict(devices=(1, 2), mloc=2048, dim=24, slate=8, window=4, trials=2,
               batch=4, tile_m=512),
    # full: Mloc=65536 at D=32 puts the per-device shard past the
    # resident kernels' VMEM budget (past_gate=1 rows) — the regime the
    # tiled local update exists for
    False: dict(devices=(1, 2, 4, 8), mloc=65536, dim=32, slate=32, window=8,
                trials=3, batch=8, tile_m=8192),
}


def main(fast_mode: bool = True, **overrides):
    cfg = dict(_PRESETS[fast_mode])
    cfg.update({k: v for k, v in overrides.items() if v is not None})
    print("name,us_per_call,derived")
    return run(cfg["devices"], cfg["mloc"], cfg["dim"], cfg["slate"],
               cfg["window"], cfg["trials"], cfg["batch"], cfg["tile_m"])


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--inner", action="store_true")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes + 1/2 devices (CI)")
    # shape flags: honored by both the outer sweep and --inner; unset
    # values fall back to the --smoke/--full preset
    ap.add_argument("--mloc", type=int, default=None)
    ap.add_argument("--dim", type=int, default=None)
    ap.add_argument("--slate", type=int, default=None)
    ap.add_argument("--window", type=int, default=None)
    ap.add_argument("--trials", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None,
                    help="user-batch B for the B>1 rows (1 = single-slate only)")
    ap.add_argument("--tile-m", type=int, default=None, dest="tile_m",
                    help="tile for the Pallas local-update rows (tm<tile>)")
    args = ap.parse_args()
    fast = args.smoke or not args.full
    if args.inner:
        # the outer sweep passes every shape flag explicitly; direct
        # --inner invocations fall back to the preset here (main() owns
        # the preset merge for the outer path)
        for k, v in _PRESETS[fast].items():
            if k != "devices" and getattr(args, k, None) is None:
                setattr(args, k, v)
        _inner(args)
    else:
        main(fast_mode=fast, mloc=args.mloc, dim=args.dim, slate=args.slate,
             window=args.window, trials=args.trials, batch=args.batch,
             tile_m=args.tile_m)
