from repro.kernels.dpp_greedy.ops import (
    dpp_greedy,
    dpp_greedy_stream_chunk,
    dpp_greedy_stream_init,
    dpp_greedy_stream_pad,
)
from repro.kernels.dpp_greedy.ref import dpp_greedy_ref
from repro.kernels.dpp_greedy.tiled import dpp_greedy_tiled
from repro.kernels.dpp_greedy.tiling import (
    VMEM_BUDGET_BYTES,
    plan_tile,
    tile_vmem_bytes,
    untiled_vmem_bytes,
)

__all__ = [
    "dpp_greedy",
    "dpp_greedy_ref",
    "dpp_greedy_stream_chunk",
    "dpp_greedy_stream_init",
    "dpp_greedy_stream_pad",
    "dpp_greedy_tiled",
    "plan_tile",
    "VMEM_BUDGET_BYTES",
    "tile_vmem_bytes",
    "untiled_vmem_bytes",
]
