"""Operations and HBM bytes of greedy MAP, from a call's shapes alone,
and the peaks of the chips the benchmark knows.

The count is of Algorithm 1 (Chen et al. 2018) itself, not of any
kernel's blocks, so it reads the same whatever implements the greedy.
For ``B`` users, ``M`` candidates, ``D`` features, ``k`` steps and a
window ``w`` (None: exact), step ``t`` (0-based) conditions on
``r_t = min(t, w)`` earlier picks and does, per user:

* the new row of the Cholesky state: ``V^T v_j`` (2·D·M operations) and
  ``C^T c_j`` over the ``r_t`` rows (2·r_t·M), the scale by ``1/d_j``
  (M);
* the marginal update ``d2 -= e^2`` (2·M) and the argmax (M).

Bytes depend on whether the call's working set (every user's ``V`` and
state) fits in the chip's on-core memory (``vmem_bytes``):

* it fits ("small", the ``feed1k`` cells): ``V`` read once, the state
  written once, per call;
* it does not ("large", the ``pool1m`` cells): every step sweeps
  ``V`` (read), the ``r_t`` state rows it conditions on (read), the
  new row (written), and ``d2`` (read and written) and the mask (read).

Least time is the larger of operations over peak FLOP/s and bytes over
HBM bandwidth.  The operation peak is the chip's bfloat16 matrix peak,
above what float32 vector arithmetic reaches, so the least time is a
true lower bound.  An algorithm that skips candidates (a lazy greedy)
does less than this count and needs the count redone.
"""
from __future__ import annotations

# Google Cloud documentation, "TPU v5e" (system architecture): 197
# TFLOP/s bf16, 16 GB HBM at 819 GB/s.  On-core vector memory (VMEM):
# 128 MiB per v5e core (JAX Pallas TPU documentation).
PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "vmem_bytes": 128 * 2**20,
        "source": "Google Cloud TPU v5e documentation",
    },
}

F32 = 4


def peaks(device_kind):
    """The peaks row of ``device_kind``; an unknown chip is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; add its row to "
            f"bench/roofline.py with a source"
        ) from None


def greedy_ops(B, M, D, k, w=None):
    """Floating-point operations of ``k`` greedy steps for ``B`` users."""
    total = 0
    for t in range(k):
        r = t if w is None else min(t, w)
        total += 2 * D * M + 2 * r * M + M + 2 * M + M
    return B * total


def greedy_bytes(B, M, D, k, w=None, vmem_bytes=None):
    """HBM bytes of ``k`` greedy steps for ``B`` users (see the module
    docstring for the small and large counts)."""
    rows = k if w is None else min(w, k)
    v_bytes = D * M * F32
    state_bytes = rows * M * F32
    if vmem_bytes is None or B * (v_bytes + state_bytes) <= vmem_bytes:
        return B * (v_bytes + state_bytes)
    total = 0
    for t in range(k):
        r = t if w is None else min(t, w)
        total += v_bytes + r * M * F32 + M * F32 + 2 * M * F32 + M * F32
    return B * total


def least_seconds(device_kind, B, M, D, k, w=None):
    """The least time the chip could take for the greedy of one call:
    ``(seconds, "ops" or "bytes")`` — which of the two bounds it."""
    p = peaks(device_kind)
    t_ops = greedy_ops(B, M, D, k, w) / p["flops_per_s"]
    t_bytes = greedy_bytes(B, M, D, k, w, p["vmem_bytes"]) / \
        p["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops > t_bytes else (t_bytes, "bytes")
