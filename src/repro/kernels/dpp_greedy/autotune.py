"""Measured kernel-geometry autotuner for the dpp_greedy Pallas seams.

``TilePolicy``'s analytical VMEM model answers "what *fits*"; it cannot
answer "what is *fastest*" — the best tile on one architecture's memory
hierarchy is not the best on another (one logical device may hide
several local memory domains).  This module measures instead of
modelling:

* **Sweep** (:func:`run_sweep`, ``python -m repro.kernels.autotune``) —
  for each tiled seam family (the exact/windowed per-step passes and
  the fused multi-step chunk kernels) over a small
  ``(D, M-bucket, w, chunk_size)`` grid, time real ``pallas_call``
  launches for every candidate tile.  Candidates are *prefiltered by
  the analytical model* (power-of-two ``LANE`` multiples up to
  ``TilePolicy.auto_tile`` — including the ``chunked=`` working-set
  distinction), so the tuner can only ever persist in-budget
  geometries.
* **Cache** (:class:`AutotuneCache`) — winners persist to an on-disk
  JSON document keyed by ``(device_kind, platform, backend, D,
  M_bucket, state_rows, windowed, chunked)`` with schema versioning and
  atomic writes (tmp file + ``os.replace``).  ``M`` is bucketed to the
  next power of two so one measurement covers a band of slate widths
  and the lookup stays monotone in ``M``.
* **Lookup ladder** (:func:`lookup_tile`, consumed by
  ``TilePolicy.decide`` when ``tile_m="auto"``) — exact key hit →
  nearest M-bucket with otherwise identical key → ``None`` (the caller
  falls back to the analytical model).  Every rung re-validates the
  entry against the VMEM budget, so a stale or hand-edited cache can
  only ever *miss*, never ship an over-budget launch; the
  ``repro.analysis`` ``autotune-cache-invalid`` rule additionally
  re-validates the persisted file against the kernels' declared
  BlockSpecs.  The ladder never raises: a missing file, unknown
  device, or corrupted JSON is a recorded miss.

Every decision lands in the PR-7 dispatch telemetry
(``autotune_cache_hits_total{kind=exact|bucket}`` /
``autotune_cache_misses_total{reason=...}`` and the ``autotune_tile_m``
gauge) so the serving fleet can see which geometry source actually ran.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import tempfile
import time
from typing import Iterable, Optional, Sequence

from repro.kernels.dpp_greedy.tiling import (
    LANE,
    MAX_AUTO_TILE,
    VMEM_BUDGET_BYTES,
    TilePolicy,
    tile_vmem_bytes,
)
from repro.obs.dispatch import record_autotune_lookup

SCHEMA_VERSION = 1
CACHE_ENV = "DPP_AUTOTUNE_CACHE"

FAMILIES = ("step_exact", "step_windowed", "chunk_exact", "chunk_windowed")


# ---------------------------------------------------------------------------
# Cache path, keying, bucketing
# ---------------------------------------------------------------------------


def default_cache_path() -> str:
    """``$XDG_CACHE_HOME``-respecting per-user default, outside any
    source tree so a tuned dev box never dirties a checkout."""
    base = os.environ.get("XDG_CACHE_HOME") or os.path.join(
        os.path.expanduser("~"), ".cache"
    )
    return os.path.join(base, "repro", "dpp_autotune.json")


def active_cache_path() -> str:
    """The cache file every lookup and sweep uses: ``$DPP_AUTOTUNE_CACHE``
    when set, else :func:`default_cache_path`."""
    return os.environ.get(CACHE_ENV) or default_cache_path()


def bucket_m(M: int) -> int:
    """Smallest power of two >= ``max(M, LANE)``.

    Monotone in ``M`` (the property tests pin this), so the cache's
    M-resolution coarsens geometrically: one measured bucket covers
    every slate width that pads into it.
    """
    if M < 1:
        raise ValueError(f"M must be >= 1, got {M}")
    b = LANE
    while b < M:
        b <<= 1
    return b


def _norm_field(value: object) -> str:
    """Normalize a free-text key field (device kind etc.): case-folded
    (``casefold``, so e.g. U+00B5 and U+03BC agree, which ``lower``
    misses), trimmed, with the ``|`` delimiter and whitespace runs
    collapsed to ``-`` so no field can smuggle a delimiter into the key."""
    s = " ".join(str(value).strip().casefold().split())
    return s.replace("|", "-").replace(" ", "-") or "unknown"


def cache_key(
    device_kind: object,
    platform: object,
    backend: object,
    D: int,
    M_bucket: int,
    state_rows: int,
    windowed: bool,
    chunked: bool,
) -> str:
    """Normalized pipe-joined cache key.  The structured fields are also
    stored on the entry; ``repro.analysis`` recomputes the key from them
    and flags any hand-edited divergence."""
    return "|".join((
        _norm_field(device_kind),
        _norm_field(platform),
        _norm_field(backend),
        f"d{int(D)}",
        f"m{int(M_bucket)}",
        f"r{int(state_rows)}",
        "w1" if windowed else "w0",
        "c1" if chunked else "c0",
    ))


def device_fingerprint() -> tuple[str, str, str]:
    """(device_kind, platform, backend) of the device the kernels run on."""
    import jax

    dev = jax.devices()[0]
    return (
        getattr(dev, "device_kind", "unknown"),
        getattr(dev, "platform", "unknown"),
        jax.default_backend(),
    )


# ---------------------------------------------------------------------------
# Persisted cache
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class AutotuneCache:
    """In-memory view of one persisted autotune cache file."""

    path: str
    entries: dict[str, dict]
    corrupt: bool = False  # file existed but did not parse/validate

    @classmethod
    def load(cls, path: str) -> "AutotuneCache":
        """Load a cache file.  Never raises: a missing file is an empty
        cache, an unreadable/foreign-schema file is an empty cache with
        ``corrupt=True`` (the lookup ladder records the miss reason)."""
        try:
            with open(path, encoding="utf-8") as fh:
                doc = json.load(fh)
        except FileNotFoundError:
            return cls(path, {})
        except (OSError, UnicodeDecodeError, ValueError):
            return cls(path, {}, corrupt=True)
        if (
            not isinstance(doc, dict)
            or doc.get("schema") != SCHEMA_VERSION
            or not isinstance(doc.get("entries"), dict)
        ):
            return cls(path, {}, corrupt=True)
        return cls(path, doc["entries"])

    def put(
        self,
        *,
        D: int,
        M_bucket: int,
        state_rows: int,
        windowed: bool,
        chunked: bool,
        tile_m: int,
        best_us: float,
        candidates: dict[int, float],
        interpret: bool,
        device: Optional[tuple[str, str, str]] = None,
    ) -> str:
        """Store one sweep winner; returns its key."""
        dk, plat, backend = device or device_fingerprint()
        key = cache_key(
            dk, plat, backend, D, M_bucket, state_rows, windowed, chunked
        )
        self.entries[key] = {
            "device_kind": dk,
            "platform": plat,
            "backend": backend,
            "D": int(D),
            "M_bucket": int(M_bucket),
            "state_rows": int(state_rows),
            "windowed": bool(windowed),
            "chunked": bool(chunked),
            "tile_m": int(tile_m),
            "best_us": float(best_us),
            "candidates": {str(t): float(us) for t, us in candidates.items()},
            "interpret": bool(interpret),
        }
        return key

    def save(self) -> None:
        """Atomic write: serialize to a tmp file in the destination
        directory, then ``os.replace`` — a concurrent reader sees either
        the old document or the new one, never a torn write."""
        d = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(d, exist_ok=True)
        doc = {"schema": SCHEMA_VERSION, "entries": self.entries}
        fd, tmp = tempfile.mkstemp(prefix=".dpp_autotune.", dir=d)
        try:
            with os.fdopen(fd, "w", encoding="utf-8") as fh:
                json.dump(doc, fh, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise


# one parsed cache per (path, mtime, size) — dispatch consults the
# ladder on every tiled decision, so lookups must not re-read the file
_LOAD_MEMO: dict[str, tuple[Optional[tuple[int, int]], AutotuneCache]] = {}


def _load_memoized(path: str) -> AutotuneCache:
    try:
        st = os.stat(path)
        stamp: Optional[tuple[int, int]] = (st.st_mtime_ns, st.st_size)
    except OSError:
        stamp = None
    hit = _LOAD_MEMO.get(path)
    if hit is not None and hit[0] == stamp:
        return hit[1]
    cache = AutotuneCache.load(path)
    _LOAD_MEMO[path] = (stamp, cache)
    return cache


# ---------------------------------------------------------------------------
# Lookup ladder (TilePolicy.decide's tile_m="auto" backend)
# ---------------------------------------------------------------------------


def _entry_tile(
    entry: object, D: int, state_rows: int, windowed: bool, chunked: bool,
    budget: int,
) -> Optional[int]:
    """The entry's tile iff it is a LANE multiple whose *model* working
    set fits the budget for the queried geometry — a stale or
    hand-edited entry degrades to a miss, never to an over-budget
    launch."""
    if not isinstance(entry, dict):
        return None
    tm = entry.get("tile_m")
    if not isinstance(tm, int) or isinstance(tm, bool):
        return None
    if tm < LANE or tm % LANE != 0 or tm > MAX_AUTO_TILE:
        return None
    if tile_vmem_bytes(D, tm, state_rows, windowed, chunked) > budget:
        return None
    return tm


def lookup_tile(
    *,
    D: int,
    M: int,
    state_rows: int,
    windowed: bool,
    chunked: bool,
    vmem_budget_bytes: int = VMEM_BUDGET_BYTES,
    path: Optional[str] = None,
) -> Optional[int]:
    """Measured tile for this device/geometry, or ``None`` (fall back to
    the analytical model).  Exact bucket hit first, then the nearest
    measured bucket with an otherwise identical key; both rungs
    re-validate against the VMEM budget.  Never raises."""
    try:
        cache = _load_memoized(path or active_cache_path())
        if cache.corrupt:
            record_autotune_lookup("miss", reason="corrupt")
            return None
        if not cache.entries:
            record_autotune_lookup("miss", reason="empty")
            return None
        dk, plat, backend = device_fingerprint()
        mb = bucket_m(M)
        key = cache_key(
            dk, plat, backend, D, mb, state_rows, windowed, chunked
        )
        tm = _entry_tile(
            cache.entries.get(key), D, state_rows, windowed, chunked,
            vmem_budget_bytes,
        )
        if tm is not None:
            record_autotune_lookup("exact", tile_m=tm)
            return tm
        # nearest bucket: same device and (D, R, windowed, chunked),
        # different M_bucket, closest in log2(M) — a key recomputed from
        # the entry's own fields must reproduce the stored key, which
        # also screens out hand-edited field/key divergence
        best: Optional[tuple[float, int, int]] = None
        for k2, e2 in cache.entries.items():
            if not isinstance(e2, dict):
                continue
            mb2 = e2.get("M_bucket")
            if not isinstance(mb2, int) or mb2 < 1 or mb2 == mb:
                continue
            if k2 != cache_key(
                dk, plat, backend, D, mb2, state_rows, windowed, chunked
            ):
                continue
            t2 = _entry_tile(
                e2, D, state_rows, windowed, chunked, vmem_budget_bytes
            )
            if t2 is None:
                continue
            dist = abs(math.log2(mb2) - math.log2(mb))
            if best is None or (dist, mb2) < best[:2]:
                best = (dist, mb2, t2)
        if best is not None:
            record_autotune_lookup("bucket", tile_m=best[2])
            return best[2]
        record_autotune_lookup("miss", reason="no_entry")
        return None
    except Exception:
        record_autotune_lookup("miss", reason="error")
        return None


# ---------------------------------------------------------------------------
# Measurement sweep
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SweepCase:
    """One tuned geometry: a seam family at a concrete
    ``(D, M, state_rows[, chunk])``.  ``M`` is measured at its bucket,
    so candidate tiles (powers of two) always divide the padded axis
    and every candidate times identical work."""

    family: str
    D: int
    M: int
    state_rows: int
    chunk: int = 8

    def __post_init__(self):
        if self.family not in FAMILIES:
            raise ValueError(
                f"unknown family {self.family!r}; expected one of {FAMILIES}"
            )

    @property
    def windowed(self) -> bool:
        return self.family.endswith("windowed")

    @property
    def chunked(self) -> bool:
        return self.family.startswith("chunk")


def candidate_tiles(
    D: int,
    state_rows: int,
    windowed: bool,
    chunked: bool,
    M_bucket: int,
    *,
    vmem_budget_bytes: int = VMEM_BUDGET_BYTES,
    limit: Optional[int] = None,
) -> list[int]:
    """Power-of-two LANE multiples up to the analytical prefilter
    (``auto_tile`` with the family's ``chunked=`` working set) and the
    bucket itself.  ``limit`` keeps only the widest N (smoke mode:
    wide tiles mean few grid steps, which is what keeps an
    interpret-mode sweep cheap)."""
    policy = TilePolicy(vmem_budget_bytes=vmem_budget_bytes)
    cap = min(
        policy.auto_tile(D, state_rows, windowed, chunked),
        M_bucket,
        MAX_AUTO_TILE,
    )
    tiles = []
    t = LANE
    while t <= cap:
        tiles.append(t)
        t <<= 1
    if limit is not None and limit > 0:
        tiles = tiles[-limit:]
    return tiles


def _case_inputs(case: SweepCase):
    """Deterministic measurement inputs at the case's bucketed M."""
    import jax.numpy as jnp
    import numpy as np

    Mb = bucket_m(case.M)
    rng = np.random.default_rng(0)
    F = rng.normal(size=(case.D, Mb)).astype(np.float32)
    F /= np.maximum(np.linalg.norm(F, axis=0, keepdims=True), 1e-12)
    rel = 1.0 + rng.uniform(size=Mb).astype(np.float32)
    return jnp.asarray(F * rel[None, :])[None]  # (1, D, Mb)


def _time_case(case: SweepCase, tile: int, trials: int,
               interpret: Optional[bool] = None) -> float:
    """Best-of-``trials`` wall seconds for one real dispatch of the
    case's seam with an explicit ``TilePolicy(tile_m=tile)`` (the
    policy object bypasses the ``DPP_TILE_M`` env override, so a sweep
    can never be hijacked by the environment it is tuning for)."""
    import jax

    from repro.kernels.dpp_greedy.ops import (
        dpp_greedy,
        dpp_greedy_stream_chunk,
        dpp_greedy_stream_init,
        dpp_greedy_stream_pad,
    )

    V = _case_inputs(case)
    policy = TilePolicy(tile_m=tile)
    if case.chunked:
        window = case.state_rows if case.windowed else None
        k = 2 * case.state_rows if case.windowed else case.state_rows
        state = dpp_greedy_stream_init(
            V, k, window=window, tile_policy=policy
        )
        Vp = dpp_greedy_stream_pad(V, state)
        fn = lambda: dpp_greedy_stream_chunk(  # noqa: E731
            Vp, state, case.chunk, eps=1e-6, tile_policy=policy,
            interpret=interpret,
        )
    else:
        window = case.state_rows if case.windowed else None
        k = 2 * case.state_rows if case.windowed else case.state_rows
        fn = lambda: dpp_greedy(  # noqa: E731
            V, k, eps=1e-6, window=window, tile_policy=policy,
            interpret=interpret,
        )
    jax.block_until_ready(fn())  # compile + warm
    best = float("inf")
    for _ in range(max(trials, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def run_sweep(
    cases: Sequence[SweepCase],
    *,
    trials: int = 2,
    limit: Optional[int] = None,
    path: Optional[str] = None,
    interpret: Optional[bool] = None,
    vmem_budget_bytes: int = VMEM_BUDGET_BYTES,
    log=None,
) -> tuple[list[dict], str]:
    """Measure every case, persist the winners (merging into whatever
    the cache file already holds), and return
    ``([{case, key, tile_m, best_us, candidates}, ...], path)``.  The
    kernels run compiled on a TPU and interpreted elsewhere; entries
    record which."""
    from repro.kernels.platform import resolve_interpret

    interpret = resolve_interpret(interpret)
    path = path or active_cache_path()
    cache = AutotuneCache.load(path)
    if cache.corrupt:
        # a broken file is replaced wholesale rather than merged into
        cache = AutotuneCache(path, {})
    device = device_fingerprint()
    results: list[dict] = []
    for case in cases:
        Mb = bucket_m(case.M)
        tiles = candidate_tiles(
            case.D, case.state_rows, case.windowed, case.chunked, Mb,
            vmem_budget_bytes=vmem_budget_bytes, limit=limit,
        )
        if not tiles:
            if log is not None:
                log(f"# skip {case.family} D={case.D} R={case.state_rows}: "
                    f"no in-budget candidate tile")
            continue
        cand: dict[int, float] = {}
        for t in tiles:
            cand[t] = _time_case(case, t, trials, interpret=interpret)
            if log is not None:
                log(f"#   {case.family} D={case.D} M={Mb} "
                    f"R={case.state_rows} tile={t}: {cand[t]*1e6:.0f}us")
        best_tile = min(cand, key=lambda t: (cand[t], t))
        key = cache.put(
            D=case.D, M_bucket=Mb, state_rows=case.state_rows,
            windowed=case.windowed, chunked=case.chunked,
            tile_m=best_tile, best_us=cand[best_tile] * 1e6,
            candidates=cand, interpret=interpret, device=device,
        )
        results.append({
            "case": case, "key": key, "tile_m": best_tile,
            "best_us": cand[best_tile] * 1e6,
            "candidates": {t: us * 1e6 for t, us in cand.items()},
        })
    cache.save()
    _LOAD_MEMO.pop(path, None)
    return results, path


def smoke_cases() -> list[SweepCase]:
    """One past-the-resident-budget geometry per seam family — sized so
    that a ``tile_m="auto"`` dispatch at these shapes actually consults
    the cache (``fig9_autotune --smoke`` evaluates exactly this grid)."""
    D, M = 64, 65536
    return [
        SweepCase("step_exact", D, M, state_rows=16),
        SweepCase("step_windowed", D, M, state_rows=8),
        SweepCase("chunk_exact", D, M, state_rows=16, chunk=8),
        SweepCase("chunk_windowed", D, M, state_rows=8, chunk=8),
    ]


def full_cases() -> list[SweepCase]:
    """The full sweep preset: every family over a (D, M-bucket, w,
    chunk_size) grid around the serving shapes."""
    cases = []
    for D in (32, 64, 128):
        for M in (65536, 131072):
            for R in (8, 16):
                cases.append(SweepCase("step_exact", D, M, state_rows=R))
                cases.append(SweepCase("step_windowed", D, M, state_rows=R))
                for chunk in (8, 16):
                    cases.append(SweepCase(
                        "chunk_exact", D, M, state_rows=R, chunk=chunk))
                    cases.append(SweepCase(
                        "chunk_windowed", D, M, state_rows=R, chunk=chunk))
    return cases


def main(argv: Optional[Iterable[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.kernels.autotune",
        description="Measure dpp_greedy kernel geometries and persist "
                    "the per-device winners for tile_m='auto'.",
    )
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sweep preset: one geometry per seam "
                         "family, widest 3 candidates, 1 trial (CI)")
    ap.add_argument("--full", action="store_true",
                    help="the full (D, M-bucket, w, chunk_size) grid")
    ap.add_argument("--out", default=None,
                    help="cache file (default: $DPP_AUTOTUNE_CACHE or "
                         "~/.cache/repro/dpp_autotune.json)")
    ap.add_argument("--trials", type=int, default=None,
                    help="timing trials per candidate (default 1 smoke, "
                         "3 full)")
    args = ap.parse_args(list(argv) if argv is not None else None)

    smoke = args.smoke or not args.full
    cases = smoke_cases() if smoke else full_cases()
    trials = args.trials if args.trials is not None else (1 if smoke else 3)
    limit = 3 if smoke else None

    print("name,us_per_call,derived")
    results, path = run_sweep(
        cases, trials=trials, limit=limit, path=args.out, log=print,
    )
    for r in results:
        case = r["case"]
        cand = ";".join(f"{t}:{us:.0f}us"
                        for t, us in sorted(r["candidates"].items()))
        print(
            f"autotune_{case.family}_D{case.D}_M{bucket_m(case.M)}"
            f"_R{case.state_rows},{r['best_us']:.1f},"
            f"tile_m={r['tile_m']};candidates={cand}"
        )
    print(f"# wrote {len(results)} entr{'y' if len(results) == 1 else 'ies'}"
          f" -> {path}")
    return 0
