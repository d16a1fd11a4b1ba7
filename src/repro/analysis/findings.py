"""Findings and suppression framework for ``repro.analysis``.

Every checker reports :class:`Finding` records — a rule id from
:data:`RULES`, a ``path:line`` anchor, and a message.  A finding is
suppressed by putting ``# repro: ignore[rule-id]`` on the anchored
line; a suppression naming an unknown rule id is itself a finding
(``bad-suppression``), so typos cannot silently disable a check.

The rule catalog (ids, what fires them, how to fix) is documented in
DESIGN.md §9.
"""
from __future__ import annotations

import dataclasses
import io
import re
import tokenize

# rule id -> one-line description (the catalog the CLI prints with
# --list-rules; DESIGN.md §9 carries the long-form entries)
RULES: dict[str, str] = {
    # -- trace-safety lint (tracelint.py) --------------------------------
    "trace-cast": (
        "float()/int()/bool()/.item() on a traced value inside a "
        "jitted or Pallas-kernel body (concretization error at trace "
        "time, or a silent host sync)"
    ),
    "trace-pyif": (
        "Python `if`/`while` on a traced value inside a jitted or "
        "Pallas-kernel body (TracerBoolConversionError; use lax.cond/"
        "jnp.where)"
    ),
    "host-sync-hot": (
        "host sync (np.asarray / device_get / block_until_ready) in a "
        "router pump hot phase outside the designated sync/materialize "
        "spans"
    ),
    "obs-nonstatic": (
        "device work (jnp/np call, .item, .block_until_ready) inside "
        "an obs.span(...) call site — hook arguments must be static/"
        "host-cheap"
    ),
    "dead-shim": (
        "import or attribute use of a removed serving shim "
        "(rerank/rerank_batch/rerank_stream/sharded_rerank/"
        "sharded_rerank_stream/_deprecated)"
    ),
    # -- jit geometry (jitgeo.py) ----------------------------------------
    "jit-static-missing": (
        "static_argnames entry that is not a parameter of the jitted "
        "function (the intended argument stays traced and re-jits are "
        "hidden)"
    ),
    "jit-static-unhashable": (
        "a static_argnames parameter that takes an unhashable or "
        "array value (jit raises at call time, or recompiles per "
        "request)"
    ),
    "router-geometry": (
        "router compiled-geometry attribute written outside __init__ "
        "(or outside its lazy `is None` guard), or more than one "
        "slot-chunk launch site — the single-compiled-geometry proof "
        "fails"
    ),
    "session-geometry": (
        "session compiled-geometry attribute written outside __init__, "
        "or more than one launch site for a resume/extend/rescore "
        "family — the session resume path could compile geometries "
        "beyond (shape, chunk)"
    ),
    # -- Pallas kernel contracts (kernels.py) ----------------------------
    "pallas-coverage-gap": (
        "a BlockSpec index_map never visits some block of its operand "
        "over the full grid (part of the array is never read/written)"
    ),
    "pallas-block-divisibility": (
        "a block shape that does not divide its (padded) operand "
        "dimension"
    ),
    "pallas-revisit-gap": (
        "an output block revisited at non-consecutive grid steps "
        "without an interpret-mode guard (compiled Mosaic does not "
        "guarantee its contents between visits)"
    ),
    "pallas-vmem-budget": (
        "a plan_tile-selectable geometry whose per-tile working set "
        "exceeds the VMEM budget"
    ),
    "pallas-vmem-model": (
        "tiling.tile_vmem_bytes undercounts the streams the kernel's "
        "BlockSpecs actually declare (plan_tile would pick an "
        "overflowing tile)"
    ),
    # -- framework -------------------------------------------------------
    "bad-suppression": (
        "`# repro: ignore[...]` naming an unknown rule id (typo would "
        "silently disable nothing — and hide that it does)"
    ),
}

_SUPPRESS_RE = re.compile(r"#\s*repro:\s*ignore\[([^\]]*)\]")


@dataclasses.dataclass(frozen=True, order=True)
class Finding:
    """One checker hit, anchored to ``path:line``."""

    path: str
    line: int
    rule: str
    message: str

    def format(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


def scan_suppressions(
    path: str, text: str
) -> tuple[dict[int, set[str]], list[Finding]]:
    """Parse ``# repro: ignore[rule-id]`` comments.

    Returns ``(suppressions, findings)`` where ``suppressions`` maps a
    1-indexed line number to the rule ids suppressed on that line, and
    ``findings`` carries a ``bad-suppression`` per unknown rule id.
    """
    supp: dict[int, set[str]] = {}
    findings: list[Finding] = []
    try:
        tokens = list(tokenize.generate_tokens(io.StringIO(text).readline))
    except (tokenize.TokenizeError, SyntaxError, IndentationError):
        return supp, findings
    # real comment tokens only — the pattern appearing in a docstring
    # or string literal is documentation, not a suppression
    for tok in tokens:
        if tok.type != tokenize.COMMENT:
            continue
        lineno = tok.start[0]
        for m in _SUPPRESS_RE.finditer(tok.string):
            for rule in (r.strip() for r in m.group(1).split(",")):
                if not rule:
                    continue
                if rule not in RULES:
                    findings.append(Finding(
                        path, lineno, "bad-suppression",
                        f"unknown rule id {rule!r} in suppression "
                        f"(known: {', '.join(sorted(RULES))})",
                    ))
                    continue
                supp.setdefault(lineno, set()).add(rule)
    return supp, findings


def apply_suppressions(
    findings: list[Finding], suppressions: dict[str, dict[int, set[str]]]
) -> list[Finding]:
    """Drop findings whose anchored line carries a matching
    suppression.  ``suppressions`` maps path -> line -> rule ids (as
    produced per-file by :func:`scan_suppressions`).  ``bad-suppression``
    itself cannot be suppressed."""
    kept = []
    for f in findings:
        if f.rule != "bad-suppression":
            by_line = suppressions.get(f.path, {})
            if f.rule in by_line.get(f.line, ()):  # noqa: SIM108
                continue
        kept.append(f)
    return kept
