"""Pallas TPU kernels for the perf-critical compute hot-spots.

Each subpackage ships ``<name>.py`` (pl.pallas_call + BlockSpec),
``ops.py`` (jit'd public wrapper with padding + fallback) and ``ref.py``
(pure-jnp oracle).  Kernels run compiled on a TPU and interpreted
elsewhere (``repro.kernels.platform``), so the CPU test sweeps run them
interpreted.
"""
from repro.kernels.dpp_greedy import dpp_greedy
from repro.kernels.fm_interaction import fm_interaction
from repro.kernels.scored_topk import scored_topk

__all__ = ["dpp_greedy", "fm_interaction", "scored_topk"]
