"""Benchmark harness — one module per paper figure/table plus the
roofline report.  Prints ``name,us_per_call,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run [--full] [--out-dir DIR]

Default is a fast mode sized for CI; ``--full`` reproduces the paper's
exact sweep sizes (M=1000, D=100, N=5..50, all three datasets).

Besides streaming the CSV to stdout, every figure writes a
``BENCH_<fig>.json`` artifact to ``--out-dir`` (default
``benchmarks/results``): the parsed rows, wall-clock elapsed, the gate
outcome (``status``/``error`` — the figures raise on red gates), and
the observability snapshot of everything that ran (kernel dispatch
counts, launched steps, marginal evaluations, jit cache misses) — the
harness keeps a ``repro.obs`` session installed so the telemetry is on
for every figure.  A figure failing its gates does not stop the rest;
the harness exits nonzero at the end if any failed.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import time

from repro import obs
from repro.obs import ObsConfig


def bench_meta():
    """Device/provenance stamp for every BENCH_<fig>.json: which
    accelerator and jax produced the numbers — enough to tell two
    artifacts apart without re-running anything.  Purely best-effort: a
    field that cannot be determined reads "unknown" rather than failing
    the benchmark that produced it."""
    meta = {
        "device_kind": "unknown", "platform": "unknown",
        "backend": "unknown", "jax": "unknown", "jaxlib": "unknown",
    }
    try:
        import jax

        meta["jax"] = jax.__version__
        try:
            import jaxlib

            meta["jaxlib"] = jaxlib.__version__
        except Exception:
            pass
        dev = jax.devices()[0]
        meta.update(
            device_kind=getattr(dev, "device_kind", "unknown"),
            platform=getattr(dev, "platform", "unknown"),
            backend=jax.default_backend(),
        )
    except Exception:
        pass
    return meta


class _Tee(io.TextIOBase):
    """Mirror writes to the real stdout while keeping a copy to parse."""

    def __init__(self, real):
        self._real = real
        self._buf = io.StringIO()

    def write(self, s):
        self._real.write(s)
        return self._buf.write(s)

    def flush(self):
        self._real.flush()

    def getvalue(self):
        return self._buf.getvalue()


def _parse_rows(text):
    rows = []
    for line in text.splitlines():
        if line.startswith("#") or line.startswith("name,"):
            continue
        parts = line.split(",", 2)
        if len(parts) != 3:
            continue
        try:
            us = float(parts[1])
        except ValueError:
            continue
        rows.append(
            {"name": parts[0], "us_per_call": us, "derived": parts[2]}
        )
    return rows


def run_fig(fig, title, fn, fast, out_dir):
    """Run one figure main, tee its CSV, and write BENCH_<fig>.json.
    Returns True when the figure's gates passed."""
    print(f"# {title}")
    if not obs.enabled():  # a figure may own (and tear down) a session
        obs.enable(ObsConfig(enabled=True))
    tee = _Tee(sys.stdout)
    t0 = time.perf_counter()
    status, error = "ok", None
    try:
        with contextlib.redirect_stdout(tee):
            fn(fast_mode=fast)
    except Exception as e:
        status, error = "failed", f"{type(e).__name__}: {e}"
        print(f"{fig}_gate,0,status=FAILED;{error}")
    doc = {
        "figure": fig,
        "status": status,
        "error": error,
        "fast_mode": fast,
        "elapsed_s": round(time.perf_counter() - t0, 3),
        "meta": bench_meta(),
        "rows": _parse_rows(tee.getvalue()),
    }
    if obs.registry() is not None:
        doc["obs"] = obs.registry().snapshot()
    path = os.path.join(out_dir, f"BENCH_{fig}.json")
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, default=str)
    return status == "ok"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--out-dir", default="benchmarks/results",
                    help="where BENCH_<fig>.json artifacts land")
    args, _ = ap.parse_known_args()
    fast = not args.full
    os.makedirs(args.out_dir, exist_ok=True)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    from benchmarks import (
        fig1_speedup,
        fig2_reference,
        fig3_tradeoff,
        fig4_windowed,
        fig5_sharded,
        fig6_streaming,
        fig7_serving,
        fig8_observability,
        fig10_session,
    )

    figures = [
        ("fig1", "Figure 1: original greedy MAP vs Div-DPP (speedup, "
         "exactness)", fig1_speedup.main),
        ("fig2", "Figure 2: MMR / Greedy / Div-DPP runtime",
         fig2_reference.main),
        ("fig3", "Figure 3: accuracy-diversity trade-off",
         fig3_tradeoff.main),
        ("fig4", "Figure 4: sliding-window vs exact, N >> w (per-step cost "
         "flat in N)", fig4_windowed.main),
        ("fig5", "Figure 5: sharded candidate-axis greedy, M/P fixed (weak "
         "scaling)", fig5_sharded.main),
        ("fig6", "Figure 6: streaming slate emission, time-to-first-chunk "
         "vs whole", fig6_streaming.main),
        ("fig7", "Figure 7: continuous-batching router, QPS vs latency "
         "percentiles", fig7_serving.main),
        ("fig8", "Figure 8: observability — pump breakdown and the "
         "recompile ledger", fig8_observability.main),
        ("fig10", "Figure 10: session delta-resume vs full re-rerank "
         "(latency, parity)", fig10_session.main),
    ]
    failed = [
        fig for fig, title, fn in figures
        if not run_fig(fig, title, fn, fast, args.out_dir)
    ]

    print("# Roofline (from dry-run artifacts, if present)")
    try:
        from benchmarks import roofline_report

        cells = roofline_report.load_cells("experiments/dryrun")
        if cells:
            ok = sum(1 for c in cells if c.get("status") == "ok")
            sk = sum(1 for c in cells if c.get("status") == "skipped")
            print(f"roofline_cells,0,ok={ok};skipped={sk};total={len(cells)}")
        else:
            print("roofline_cells,0,none (run repro.launch.run_dryruns)")
    except Exception as e:  # pragma: no cover
        print(f"roofline_cells,0,error={e}")

    obs.disable()
    if failed:
        raise SystemExit(f"figures with failed gates: {failed}")


if __name__ == "__main__":
    main()
