"""AOT compiles of the reranking path's Pallas kernels for a TPU v5e.

Interpret mode (every other kernel test) cannot see what the Mosaic
compiler refuses: lane-axis dynamic slices, scalar stores to VMEM, a
working set over the scoped-VMEM limit.  These tests compile each
``dpp_greedy`` kernel family at the served widths for one chip of a
described ``v5e:2x2`` topology — nothing runs, no chip is needed — and
the geometries ``plan_tile`` picks at the edge of its VMEM budget; and
the candidate-sharded greedy over all four chips of that topology.
Each compile also checks that the program names its kernel by the
family's ``pallas_call`` name (``dpp_{resident,step,chunk}_{exact,
windowed}``), which the benchmark's trace readers match.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU compiler library, and every
test worker imports this file.
"""
from __future__ import annotations

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.dpp_greedy.dpp_greedy import dpp_greedy_kernel
from repro.kernels.dpp_greedy.tiled import (
    dpp_greedy_tiled,
    fused_chunk_exact,
    fused_chunk_windowed,
)
from repro.kernels.dpp_greedy.tiling import (
    LANE,
    VMEM_BUDGET_BYTES,
    plan_tile,
    round_up,
    untiled_vmem_bytes,
)

# served widths: a shortlist of 1000 at D=100 (padded to the (8, 128)
# tile), B=8 users; the past-the-budget pool of 131072 at D=64
B, D, M, K, W, CHUNK = 8, round_up(100, 8), round_up(1000, LANE), 50, 8, 10
D_BIG, M_BIG = 64, 131072


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """``shape(dims, dtype)`` -> an abstract operand on one v5e chip.

    The persistent compilation cache is off while these compile: an
    entry written for a described chip cannot be read back without one.
    """
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda dims, dtype=jnp.float32: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip
    )
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *operands):
    return jax.jit(fn).lower(*operands).compile()


def _kernel_names(compiled):
    """The instruction names of the Pallas kernels in a compiled
    program, without their numbers (``%dpp_step_exact.7 = ...``)."""
    return set(re.findall(
        r'%([\w-]+)(?:\.\d+)? = [^\n]*custom_call_target="tpu_custom_call"',
        compiled.as_text(),
    ))


def _family(kind, windowed):
    return f"dpp_{kind}_{'windowed' if windowed else 'exact'}"


def _resident(shape, k, window, Bn, Dn, Mn):
    fn = functools.partial(
        dpp_greedy_kernel, k=k, window=window, interpret=False
    )
    compiled = _compile(fn, shape((Bn, Dn, Mn)), shape((Bn, Mn)))
    windowed = window is not None and window < k
    assert _kernel_names(compiled) == {_family("resident", windowed)}
    return compiled


def _tiled(shape, k, window, tile, Dn, Mn):
    fn = functools.partial(
        dpp_greedy_tiled, k=k, window=window, tile_m=tile, interpret=False
    )
    compiled = _compile(fn, shape((1, Dn, Mn)), shape((1, Mn)))
    windowed = window is not None and window < k
    assert _kernel_names(compiled) == {_family("step", windowed)}
    return compiled


def _chunk(shape, R, windowed, Dn, tile):
    ops = [shape((1, Dn, tile)), shape((1, R, tile)), shape((1, tile))]
    if windowed:
        fn = functools.partial(
            fused_chunk_windowed, t0=0, chunk=CHUNK, eps=1e-3, w=R,
            tile_m=tile, interpret=False,
        )
        ops.append(shape((1, R), jnp.int32))
    else:
        fn = functools.partial(
            fused_chunk_exact, t0=0, chunk=CHUNK, eps=1e-3, tile_m=tile,
            interpret=False,
        )
    compiled = _compile(
        lambda *a: fn(*a, stopped=jnp.zeros((1,), bool)), *ops
    )
    assert _kernel_names(compiled) == {_family("chunk", windowed)}
    return compiled


def _policy_tile(Dn, R, windowed, chunked=False):
    """The tile plan_tile picks for a pool far past the budget."""
    mode, tm = plan_tile(Dn, 1 << 22, R, windowed, chunked=chunked)
    assert mode == "tiled"
    return tm


def test_resident_exact_compiles(shape):
    _resident(shape, K, None, B, D, M)


def test_resident_windowed_compiles(shape):
    _resident(shape, 2 * K, W, B, D, M)


def test_tiled_exact_compiles(shape):
    tile = _policy_tile(D_BIG, K, False)
    _tiled(shape, K, None, tile, D_BIG, round_up(M_BIG, tile))


def test_tiled_windowed_compiles(shape):
    tile = _policy_tile(D_BIG, W, True)
    _tiled(shape, K, W, tile, D_BIG, round_up(M_BIG, tile))


def test_fused_chunk_exact_one_tile_compiles(shape):
    _chunk(shape, K, False, D, M)


def test_fused_chunk_windowed_one_tile_compiles(shape):
    _chunk(shape, W, True, D, M)


def test_compiled_multitile_fused_chunk_raises(shape):
    """Past one tile the fused chunk's cross-step state sits in output
    blocks revisited non-consecutively; compiling that must raise, never
    interpret or fall back quietly."""
    with pytest.raises(NotImplementedError, match="single whole-M tile"):
        _compile(
            lambda V, C, d2: fused_chunk_exact(
                V, C, d2, 0, jnp.zeros((1,), bool), chunk=CHUNK, eps=1e-3,
                tile_m=LANE, interpret=False,
            ),
            shape((1, D, 2 * LANE)), shape((1, K, 2 * LANE)),
            shape((1, 2 * LANE)),
        )


@pytest.mark.parametrize(
    "Dn,R,windowed", [(8, 8, True), (104, 50, False), (256, 128, False)]
)
def test_budget_edge_geometries_compile(shape, Dn, R, windowed):
    """The widest resident M (also the whole-M tile a stream of that
    pool chunks with), per-step tile and chunk tile the VMEM budget
    admits all fit what the compiler accepts."""
    Mr = LANE
    while untiled_vmem_bytes(Dn, Mr + LANE, R) <= VMEM_BUDGET_BYTES:
        Mr += LANE
    k = 2 * R if windowed else R
    w = R if windowed else None
    _resident(shape, k, w, 1, Dn, Mr)
    _chunk(shape, R, windowed, Dn, Mr)
    tile = _policy_tile(Dn, R, windowed)
    _tiled(shape, k, w, tile, Dn, 2 * tile)
    _chunk(shape, R, windowed, Dn, _policy_tile(Dn, R, windowed, True))


@pytest.mark.parametrize("window", [None, 8], ids=["exact", "windowed"])
def test_sharded_default_tile_compiles_the_step_kernel(shape, topo,
                                                       monkeypatch, window):
    """``dpp_greedy_sharded`` with no ``tile_m``, over the four chips of
    the described topology at the ``pool1m`` cell's width (D=32, k=20)
    and a reduced pool: each chip runs the Pallas step kernel, and no
    jnp step body (its products are ``dot_general``s) is left.

    Code that asks the platform sees this process's CPU, so the test
    steers it to the compiled platform the topology describes."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import sharded
    from repro.kernels.dpp_greedy import tiled

    def compiled_platform(interpret=None):
        return False if interpret is None else bool(interpret)

    monkeypatch.setattr(sharded, "resolve_interpret", compiled_platform)
    monkeypatch.setattr(tiled, "resolve_interpret", compiled_platform)
    mesh = Mesh(np.array(topo.devices), ("data",))
    V = jax.ShapeDtypeStruct((32, 4 * 65536), jnp.float32,
                             sharding=NamedSharding(mesh, P(None, "data")))
    compiled = _compile(
        lambda V: sharded.dpp_greedy_sharded(V, 20, mesh=mesh,
                                             window=window), V)
    assert _kernel_names(compiled) == {_family("step", window is not None)}
    assert "dot_general" not in compiled.as_text()


@pytest.mark.parametrize("masked", [False, True],
                         ids=["unmasked", "masked"])
def test_batched_front_door_greedy_program_compiles(shape, monkeypatch,
                                                    masked):
    """``Reranker.rerank``'s compiled greedy program for a user batch at
    the feed's served widths (8 users, a shortlist of 1000 at D=100,
    k=50): the users map onto the resident kernel in one program, and
    no jnp step body (its products are ``dot_general``s) is left.

    Code that asks the platform sees this process's CPU, so the test
    steers the kernel dispatch to the compiled platform the topology
    describes, and drops the program it traced either way."""
    from repro.kernels.dpp_greedy import ops
    from repro.serving import DPPRerankConfig, api

    def compiled_platform(interpret=None):
        return False if interpret is None else bool(interpret)

    monkeypatch.setattr(ops, "resolve_interpret", compiled_platform)
    Dn, C = 100, 1000
    spec = DPPRerankConfig(slate_size=K, shortlist=C, alpha=3.0, eps=1e-3,
                           use_kernel=True).greedy_spec()
    assert ops.dpp_greedy_plan(Dn, C, K)[:2] == ("resident", None)
    api._greedy_program.clear_cache()
    try:
        compiled = _compile(
            lambda V, m, ids: api._greedy_program(V, m, ids, spec=spec),
            shape((B, Dn, C)), shape((B, C), jnp.bool_) if masked else None,
            shape((B, C), jnp.int32),
        )
    finally:
        api._greedy_program.clear_cache()
    assert _kernel_names(compiled) == {_family("resident", False)}
    assert "dot_general" not in compiled.as_text()
