"""Logical-axis sharding context.

Model code never names mesh axes directly; it constrains activations by
*logical* names ("batch", "seq", "model", "experts", "vocab", ...) via
``constrain``.  The launch layer installs a rule table mapping logical
names to mesh axes; outside any mesh (unit tests, single-device smoke
runs) everything is a no-op.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Mapping, Optional, Sequence, Union

import jax
from jax.sharding import PartitionSpec as P

AxisVal = Union[None, str, Sequence[str]]

_RULES: contextvars.ContextVar[Optional[Mapping[str, AxisVal]]] = contextvars.ContextVar(
    "axis_rules", default=None
)

# Canonical rule tables (sharding rules; DESIGN.md §4).  "dp" is the pure-data axis name
# set; on the multi-pod mesh the pod axis composes with data.
def single_pod_rules() -> Mapping[str, AxisVal]:
    return {
        "batch": ("data",),
        "fsdp": ("data",),
        "model": "model",
        "experts": "model",
        "vocab": "model",
        "heads": "model",
        "kv_seq": "model",
        "ff": "model",
        "rows": "model",  # embedding-table rows
        "nodes": ("data", "model"),  # GNN full-graph node sharding
        "edges": ("data", "model"),
    }


def multi_pod_rules() -> Mapping[str, AxisVal]:
    return {
        "batch": ("pod", "data"),
        "fsdp": ("pod", "data"),
        "model": "model",
        "experts": "model",
        "vocab": "model",
        "heads": "model",
        "kv_seq": "model",
        "ff": "model",
        "rows": "model",
        "nodes": ("pod", "data", "model"),
        "edges": ("pod", "data", "model"),
    }


def fsdp_ep_rules(multi_pod: bool) -> Mapping[str, AxisVal]:
    """Beyond-paper LM profile (§Perf): no tensor parallelism — dense
    params ZeRO-3-sharded over ALL axes (gathered per layer), activations
    sharded batch x sequence (the "model" axis carries SEQUENCE, not
    heads), experts stay expert-parallel on "model".  Kills the
    per-layer Megatron activation all-reduces."""
    dp = ("pod", "data") if multi_pod else ("data",)
    return {
        "batch": dp,
        "seq": "model",
        "fsdp": dp + ("model",),
        "fsdp_expert": dp,  # experts already consume "model"
        "model": "model",
        "experts": "model",
        "vocab": "model",
        "heads": None,
        "kv_seq": "model",
        "ff": None,
        "rows": "model",
        "nodes": dp + ("model",),
        "edges": dp + ("model",),
    }


def recsys_a2a_rules(multi_pod: bool) -> Mapping[str, AxisVal]:
    """Beyond-paper recsys profile (§Perf): batch sharded over ALL axes,
    embedding rows exchanged via all_to_all instead of dense psum."""
    base = dict(multi_pod_rules() if multi_pod else single_pod_rules())
    base["batch"] = (("pod", "data", "model") if multi_pod
                     else ("data", "model"))
    base["rows"] = base["batch"]  # table rows over the full device grid
    return base


_MESH: contextvars.ContextVar = contextvars.ContextVar("mesh", default=None)


@contextlib.contextmanager
def axis_rules(rules: Optional[Mapping[str, AxisVal]], mesh=None):
    tok = _RULES.set(rules)
    tok_m = _MESH.set(mesh)
    try:
        yield
    finally:
        _RULES.reset(tok)
        _MESH.reset(tok_m)


def current_mesh():
    return _MESH.get()


def data_axis_names() -> tuple:
    """Concrete mesh axes behind the logical batch/data axis."""
    rules = _RULES.get()
    if rules is None:
        return ()
    v = rules.get("batch")
    if v is None:
        return ()
    return (v,) if isinstance(v, str) else tuple(v)


def current_rules() -> Optional[Mapping[str, AxisVal]]:
    return _RULES.get()


def logical_to_spec(*names: Optional[str]) -> P:
    rules = _RULES.get()
    if rules is None:
        return P()
    resolved = []
    for n in names:
        if n is None:
            resolved.append(None)
        else:
            r = rules.get(n)
            resolved.append(tuple(r) if isinstance(r, (list, tuple)) else r)
    return P(*resolved)


def constrain(x, *names: Optional[str]):
    """with_sharding_constraint by logical axis names; no-op without rules."""
    if _RULES.get() is None:
        return x
    mesh = _MESH.get()
    spec = logical_to_spec(*names)
    if mesh is not None:
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(mesh, spec)
        )
    return jax.lax.with_sharding_constraint(x, spec)


def axis_size(logical: str) -> int:
    """Product of mesh-axis sizes a logical name maps to (1 outside mesh)."""
    rules = _RULES.get()
    if rules is None:
        return 1
    val = rules.get(logical)
    if val is None:
        return 1
    names = (val,) if isinstance(val, str) else tuple(val)
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return 1
    size = 1
    for n in names:
        size *= mesh.shape.get(n, 1)
    return size


def model_axis_name() -> Optional[str]:
    """Concrete mesh-axis name for the logical 'model' axis (or None)."""
    rules = _RULES.get()
    if rules is None:
        return None
    v = rules.get("model")
    if isinstance(v, (list, tuple)):
        return v[0] if v else None
    return v
