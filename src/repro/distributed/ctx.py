"""Activation-sharding context.

Model code is mesh-agnostic; the launch layer wraps step functions in
``activation_sharding(mesh)`` so that ``shard_act(x, 'batch', None, ...)``
calls inside the models become ``with_sharding_constraint``s against the
production mesh (and no-ops in single-device tests).

Dim tags: 'batch' -> the ('pod','data') super-axis; 'model' -> the tensor
axis; None -> unsharded.  A tag is dropped automatically when the dim size
is not divisible by the mesh axis size, so the same model code is legal for
every architecture/shape combination.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "activation_sharding", default=None)


@contextlib.contextmanager
def activation_sharding(mesh: Optional[Mesh]):
    token = _CTX.set(mesh)
    try:
        yield
    finally:
        _CTX.reset(token)


def _axsize(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _expand(tag, ba):
    """'batch' -> the (pod, data) super-axis; tuples may mix tags."""
    if tag is None:
        return None
    if tag == "batch":
        return ba
    if isinstance(tag, str):
        return (tag,)
    out: tuple = ()
    for t in tag:
        e = _expand(t, ba)
        if e:
            out += e
    return out


def shard_act(x: jax.Array, *dims) -> jax.Array:
    """Constrain ``x`` so dim i follows dims[i].

    Tags: 'batch' (the ('pod','data') super-axis), a mesh axis name, a tuple
    of tags, or None.  Tags are dropped per-dim when the size is not
    divisible or the axis is already used — the same model code stays legal
    for every architecture/shape/mesh combination.
    """
    mesh = _CTX.get()
    if mesh is None:
        return x
    ba = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    spec = []
    used: set = set()
    for tag, size in zip(dims, x.shape):
        names = _expand(tag, ba)
        if not names:
            spec.append(None)
            continue
        names = tuple(n for n in names if n in mesh.axis_names)
        if (not names or any(n in used for n in names)
                or size % _axsize(mesh, names) != 0):
            spec.append(None)
            continue
        used.update(names)
        spec.append(names if len(names) > 1 else names[0])
    spec += [None] * (x.ndim - len(spec))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))


def gathered(w: jax.Array) -> jax.Array:
    """ZeRO-3 weight gather: constrain a stored-sharded weight to fully
    replicated right before use, so GSPMD inserts one all-gather per layer
    (and the transposed reduce-scatter for its gradient) instead of
    all-reducing activation-sized partial products."""
    mesh = _CTX.get()
    if mesh is None:
        return w
    return jax.lax.with_sharding_constraint(
        w, NamedSharding(mesh, P(*([None] * w.ndim))))
