"""Tests for ``repro.analysis`` — the static checker is itself checked
from both sides:

* **negative oracle** — the seeded-violation corpus in
  tests/fixtures/analysis/ must make every AST rule fire at exactly the
  planted lines (golden findings), suppressions must silence exactly
  their rule, and a typo'd rule id must be a finding rather than a
  silent no-op;
* **positive oracle** — the whole repo (src/, benchmarks/, examples/)
  must come back with zero findings, the kernel contract sweep must
  cover every family, and the router geometry proof must report exactly
  one reachable compiled geometry (the static fig8 counterpart);
* **kernel rules** — driven through corrupted seams: a broken
  ``index_map`` must surface as pallas-coverage-gap, a non-dividing
  block as pallas-block-divisibility, removing the interpret guard as
  pallas-revisit-gap, and a stale/undercounting VMEM model as
  pallas-vmem-model / pallas-vmem-budget;
* **fix regressions** — the two real findings this PR fixed stay
  fixed: the fused chunk kernels refuse to compile multi-tile, and the
  chunked VMEM model counts the state write-back stream.
"""
import ast
import dataclasses
from pathlib import Path

import pytest

from repro.analysis import RULES, run_analysis
from repro.analysis import jitgeo
from repro.analysis import kernels as ak
from repro.analysis.cli import main as cli_main
from repro.analysis.findings import (
    Finding,
    apply_suppressions,
    scan_suppressions,
)

ROOT = Path(__file__).resolve().parents[1]
FIXTURES = Path(__file__).resolve().parent / "fixtures" / "analysis"

AST_RULES = {
    "trace-cast", "trace-pyif", "host-sync-hot", "obs-nonstatic",
    "dead-shim", "jit-static-missing", "jit-static-unhashable",
    "router-geometry", "session-geometry", "bad-suppression",
}
KERNEL_RULES = {
    "pallas-coverage-gap", "pallas-block-divisibility",
    "pallas-revisit-gap", "pallas-vmem-budget", "pallas-vmem-model",
}

# the corpus' planted violations: (fixture file, line, rule)
GOLDEN = {
    ("fx_dead_shim.py", 2, "dead-shim"),
    ("fx_dead_shim.py", 3, "dead-shim"),
    ("fx_dead_shim.py", 11, "dead-shim"),
    ("fx_host_sync.py", 8, "host-sync-hot"),
    ("fx_host_sync.py", 9, "host-sync-hot"),
    ("fx_jit_static.py", 9, "jit-static-missing"),
    ("fx_jit_static.py", 9, "jit-static-unhashable"),
    ("fx_jit_static.py", 17, "jit-static-unhashable"),
    ("fx_obs_nonstatic.py", 6, "obs-nonstatic"),
    ("fx_obs_nonstatic.py", 8, "obs-nonstatic"),
    ("fx_router_geometry.py", 13, "router-geometry"),
    ("fx_router_geometry.py", 20, "router-geometry"),
    ("fx_router_geometry.py", 26, "router-geometry"),
    ("fx_session_geometry.py", 16, "session-geometry"),
    ("fx_session_geometry.py", 22, "session-geometry"),
    ("fx_suppressed.py", 15, "bad-suppression"),
    ("fx_suppressed.py", 15, "trace-pyif"),
    ("fx_trace_cast.py", 9, "trace-cast"),
    ("fx_trace_cast.py", 14, "trace-cast"),
    ("fx_trace_cast.py", 18, "trace-cast"),
    ("fx_trace_pyif.py", 7, "trace-pyif"),
    ("fx_trace_pyif.py", 15, "trace-pyif"),
}


@pytest.fixture(scope="module")
def corpus():
    findings, summary = run_analysis([str(FIXTURES)], kernel_checks=False)
    return findings, summary


# --------------------------------------------------------------------------
# Negative oracle: the seeded corpus
# --------------------------------------------------------------------------


def test_rule_catalog_is_complete():
    assert set(RULES) == AST_RULES | KERNEL_RULES


def test_corpus_matches_golden_findings(corpus):
    findings, _ = corpus
    got = {(Path(f.path).name, f.line, f.rule) for f in findings}
    assert got == GOLDEN


def test_every_ast_rule_fires_on_the_corpus(corpus):
    findings, _ = corpus
    assert {f.rule for f in findings} == AST_RULES


def test_cli_exits_nonzero_on_corpus(capsys):
    rc = cli_main([str(FIXTURES), "--no-kernel-checks",
                   "--error-on-findings"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "finding(s)" in out


def test_clean_file_exits_zero(tmp_path, capsys):
    clean = tmp_path / "clean.py"
    clean.write_text("import jax\n\n\n@jax.jit\ndef f(x):\n    return x\n")
    assert cli_main([str(clean)]) == 0
    assert "0 finding(s)" in capsys.readouterr().out


def test_list_rules(capsys):
    assert cli_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in RULES:
        assert rule in out


def test_suppression_silences_only_its_line(corpus):
    findings, _ = corpus
    suppressed = [f for f in findings
                  if Path(f.path).name == "fx_suppressed.py"]
    # line 8 (`tolerated`) is validly suppressed: nothing anchors there
    assert all(f.line != 8 for f in suppressed)
    # the typo'd suppression on line 15 silences nothing and is itself
    # a finding
    assert {(f.line, f.rule) for f in suppressed} == {
        (15, "bad-suppression"), (15, "trace-pyif"),
    }


def test_unknown_rule_id_is_rejected():
    supp, bad = scan_suppressions(
        "x.py", "a = 1  # repro: ignore[no-such-rule]\n"
    )
    assert supp == {}
    assert [f.rule for f in bad] == ["bad-suppression"]
    assert "no-such-rule" in bad[0].message


def test_suppression_in_docstring_is_not_a_suppression():
    supp, bad = scan_suppressions(
        "x.py", '"""docs mention # repro: ignore[trace-cast]"""\n'
    )
    assert supp == {} and bad == []


def test_bad_suppression_cannot_be_suppressed():
    f = Finding("x.py", 3, "bad-suppression", "typo")
    kept = apply_suppressions([f], {"x.py": {3: {"bad-suppression"}}})
    assert kept == [f]


# --------------------------------------------------------------------------
# Positive oracle: the repo itself is clean
# --------------------------------------------------------------------------


def test_whole_repo_has_zero_findings():
    paths = [str(ROOT / p) for p in ("src", "benchmarks", "examples")
             if (ROOT / p).exists()]
    findings, summary = run_analysis(paths)
    assert findings == [], "\n".join(f.format() for f in findings)
    kc = summary["kernel_contracts"]
    assert kc is not None
    assert sorted(kc["families"]) == [
        "chunk_exact", "chunk_windowed", "step_exact", "step_windowed",
    ]
    assert kc["geometries"] == (len(ak.SWEEP_D) * len(ak.SWEEP_R)
                                * len(kc["families"]))


def test_router_geometry_proof():
    src = ROOT / "src" / "repro" / "serving" / "router.py"
    tree = ast.parse(src.read_text(), filename=str(src))
    summaries = [s for s in (
        jitgeo.router_geometry_summary(n) for n in ast.walk(tree)
        if isinstance(n, ast.ClassDef)
    ) if s is not None]
    assert len(summaries) == 1
    proof = summaries[0]
    assert proof["violations"] == []
    assert proof["launch_sites"] == 1
    assert proof["reachable_geometries"] == 1


def test_corpus_router_summaries(corpus):
    _, summary = corpus
    by_class = {s["class"]: s for s in summary["router_geometry"]}
    assert by_class["WobblyRouter"]["reachable_geometries"] is None
    assert by_class["WobblyRouter"]["launch_sites"] == 2
    assert by_class["SteadyRouter"]["reachable_geometries"] == 1


def test_session_geometry_proof():
    src = ROOT / "src" / "repro" / "serving" / "session.py"
    tree = ast.parse(src.read_text(), filename=str(src))
    summaries = [s for s in (
        jitgeo.session_geometry_summary(n) for n in ast.walk(tree)
        if isinstance(n, ast.ClassDef)
    ) if s is not None]
    assert len(summaries) == 1
    proof = summaries[0]
    assert proof["class"] == "RerankSession"
    assert proof["violations"] == []
    assert proof["launch_sites"] == {
        "greedy_chunk": 1,
        "greedy_state_extend": 1,
        "greedy_state_rescore": 1,
    }
    assert proof["reachable_geometries"] == 1


def test_corpus_session_summaries(corpus):
    _, summary = corpus
    by_class = {s["class"]: s for s in summary["session_geometry"]}
    assert by_class["WobblySession"]["reachable_geometries"] is None
    assert by_class["WobblySession"]["launch_sites"]["greedy_state_extend"] == 2
    assert by_class["SteadySession"]["reachable_geometries"] == 1
    assert by_class["SteadySession"]["geometry_attrs"] == ["spec"]


# --------------------------------------------------------------------------
# Kernel contract rules, driven through corrupted seams
# --------------------------------------------------------------------------


def _drive(monkeypatch, family="chunk_exact", D=64, R=48, corrupt=None):
    """Drive one kernel family with the recorder patched in (and an
    optional corruption applied first); returns the recorded seam."""
    from repro.kernels.dpp_greedy import tiled

    if corrupt is not None:
        corrupt(tiled, monkeypatch)
    rec = ak._Recorder()
    monkeypatch.setattr(tiled.pl, "pallas_call", rec)
    return ak._drive_family(tiled, family, D, R, rec)


def test_intact_seams_are_clean(monkeypatch):
    for family in ("step_exact", "step_windowed", "chunk_exact",
                   "chunk_windowed"):
        seam = _drive(monkeypatch, family=family)
        assert ak.check_launch_geometry(seam) == []
        assert ak.check_vmem_contract(seam) == []


def test_corrupted_index_map_is_a_coverage_gap(monkeypatch):
    """The end-to-end corrupted-index_map test: pin every streamed tile
    to block 0 and the checker must see that block 1 of a 2-tile sweep
    is never visited."""
    from jax.experimental import pallas as pl

    def corrupt(tiled, mp):
        mp.setattr(tiled, "_tile_spec", lambda rows, tile_m: pl.BlockSpec(
            (None, rows, tile_m), lambda b, i: (b, 0, 0)))

    seam = _drive(monkeypatch, family="step_exact", corrupt=corrupt)
    findings = ak.check_launch_geometry(seam)
    assert "pallas-coverage-gap" in {f.rule for f in findings}
    assert any("never visited" in f.message for f in findings)


def test_non_dividing_block_fires(monkeypatch):
    from jax.experimental import pallas as pl

    spec = pl.BlockSpec((None, 8, 100), lambda b, i: (b, 0, i))
    rec = ak.RecordedCall(
        name="synthetic", grid=(1, 2), in_specs=(spec,), out_specs=(),
        in_shapes=((1, 8, 256),), out_shapes=(), interpret=True,
    )
    seam = ak.DrivenSeam(
        call=rec, family="synthetic", D=8, state_rows=8, windowed=False,
        chunked=False, path="synthetic.py", line=1,
    )
    rules = {f.rule for f in ak.check_launch_geometry(seam)}
    assert "pallas-block-divisibility" in rules


def test_unguarded_revisit_gap_fires(monkeypatch):
    """Remove the interpret guard and the fused chunk kernels' cross-
    step state in non-consecutively revisited blocks becomes a
    finding — the checker proves the guard is what makes them safe."""
    from repro.kernels.dpp_greedy import tiled

    def corrupt(tiled_mod, mp):
        mp.setattr(tiled_mod, "_require_interpret_for_multitile",
                   lambda *a, **k: None)

    for family in ("chunk_exact", "chunk_windowed"):
        seam = _drive(monkeypatch, family=family, corrupt=corrupt)
        rules = {f.rule for f in ak.check_launch_geometry(seam)}
        assert "pallas-revisit-gap" in rules, family
    assert tiled._require_interpret_for_multitile is not None


def test_stale_vmem_model_fires(monkeypatch):
    """Re-create the pre-fix bug: account a chunk seam with the
    per-step model (chunked=False) and the state write-back stream is
    undercounted."""
    seam = _drive(monkeypatch, family="chunk_exact", D=64, R=48)
    stale = dataclasses.replace(seam, chunked=False)
    assert "pallas-vmem-model" in {
        f.rule for f in ak.check_vmem_contract(stale)
    }
    assert ak.check_vmem_contract(seam) == []


def test_undercounting_model_breaks_the_budget(monkeypatch):
    """If tile_vmem_bytes undercounted the streams, plan_tile would
    pick a tile whose recorded working set overflows VMEM — the budget
    rule catches it from the BlockSpec actuals."""
    from repro.kernels.dpp_greedy import tiling

    seam = _drive(monkeypatch, family="chunk_exact", D=64, R=48)
    monkeypatch.setattr(
        tiling, "tile_vmem_bytes",
        lambda D, tile_m=0, state_rows=0, windowed=False, chunked=False:
        8 * tile_m,
    )
    rules = {f.rule for f in ak.check_vmem_contract(seam)}
    assert "pallas-vmem-budget" in rules


# --------------------------------------------------------------------------
# Regressions for the real findings this PR fixed
# --------------------------------------------------------------------------


def test_fused_chunk_refuses_to_compile_multitile():
    """Fix regression (pallas-revisit-gap): compiled Mosaic does not
    preserve non-consecutively revisited output blocks, so the fused
    chunk kernels must refuse interpret=False with nt > 1."""
    import jax.numpy as jnp

    from repro.kernels.dpp_greedy import tiled

    B, D, R, Mp = 1, 8, 8, 256
    V = jnp.zeros((B, D, Mp), jnp.float32)
    C = jnp.zeros((B, R, Mp), jnp.float32)
    d2 = jnp.zeros((B, Mp), jnp.float32)
    stopped = jnp.zeros((B,), bool)
    with pytest.raises(NotImplementedError, match="single whole-M tile"):
        tiled.fused_chunk_exact.__wrapped__(
            V, C, d2, 0, stopped, chunk=2, eps=1e-3, tile_m=128,
            interpret=False,
        )
    win = jnp.full((B, R), -1, jnp.int32)
    with pytest.raises(NotImplementedError, match="single whole-M tile"):
        tiled.fused_chunk_windowed.__wrapped__(
            V, C, d2, win, 0, stopped, chunk=2, eps=1e-3, w=R,
            tile_m=128, interpret=False,
        )
    # single whole-M tile (revisits consecutive) and interpret mode
    # stay allowed
    tiled._require_interpret_for_multitile(False, 1)
    tiled._require_interpret_for_multitile(True, 4)


def test_chunked_vmem_model_counts_state_writeback():
    """Fix regression (pallas-vmem-model): the fused chunk kernels
    stream the full (state_rows, tile_m) Cholesky block back out every
    step; the model must count it."""
    from repro.kernels.dpp_greedy import tiling

    D, R, tm = 64, 128, 512
    per_step = tiling.tile_vmem_bytes(D, tm, R, windowed=False,
                                      chunked=False)
    chunked = tiling.tile_vmem_bytes(D, tm, R, windowed=False,
                                     chunked=True)
    Rp = tiling.round_up(R, tiling.SUBLANE)
    assert chunked - per_step == 4 * 2 * (Rp - tiling.SUBLANE) * tm
    # windowed already streamed the full state; chunked adds nothing
    assert tiling.tile_vmem_bytes(D, tm, R, windowed=True, chunked=True) \
        == tiling.tile_vmem_bytes(D, tm, R, windowed=True, chunked=False)


def test_stream_tile_fits_chunked_budget():
    """Fix regression: the streaming executor sizes its tile with the
    chunked model, so the tile it picks fits the budget under the
    fused chunk kernels' real working set."""
    from repro.kernels.dpp_greedy import ops, tiling

    D, M, R = 64, 1 << 20, 128
    tile, Mp = ops._stream_tile(D, M, R, False, None)
    assert tile > 0 and Mp % tile == 0
    assert tiling.tile_vmem_bytes(D, tile, R, windowed=False,
                                  chunked=True) \
        <= tiling.VMEM_BUDGET_BYTES
