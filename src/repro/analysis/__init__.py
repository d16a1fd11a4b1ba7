"""Static analysis for the repro codebase (``python -m repro.analysis``).

Three checker families over one findings/suppression framework:

* ``repro.analysis.kernels`` — Pallas kernel contracts: BlockSpec
  coverage/divisibility, revisit contiguity (the Mosaic hazard), and
  the VMEM tile model checked against the specs the kernels
  actually declare;
* ``repro.analysis.jitgeo`` — jit boundary hygiene and the router's
  single-compiled-geometry proof;
* ``repro.analysis.tracelint`` — AST trace-safety lint (tracer leaks,
  hot-path host syncs, non-static obs hooks, dead shims).

Findings carry rule ids (``repro.analysis.findings.RULES``) anchored
to ``path:line`` and are suppressible with ``# repro: ignore[rule-id]``.
Rule catalog: DESIGN.md §9.
"""
from repro.analysis.cli import main, run_analysis
from repro.analysis.findings import RULES, Finding

__all__ = ["Finding", "RULES", "main", "run_analysis"]
