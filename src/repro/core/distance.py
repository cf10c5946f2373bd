"""Distance computations.

Everything is squared-L2 (monotone in L2, so rankings are identical and we
avoid sqrt everywhere, as DiskANN does).  The batched form is written as
``||q||^2 - 2 q.x + ||x||^2`` so that the inner product lands on the MXU; the
Pallas kernel in ``repro.kernels.l2_distance`` implements the same contraction
with explicit VMEM tiling and is used by the ops-layer when enabled.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

INF = jnp.inf
INVALID = -1  # sentinel node id
# f32 matmuls on a TPU default to one bf16 pass; distances here rank
# neighbors and define ground truth, so they ask for full f32 precision
# (a no-op on CPU, where f32 is already exact).
HIGHEST = jax.lax.Precision.HIGHEST


def l2_sq(a: jax.Array, b: jax.Array) -> jax.Array:
    """Squared L2 between broadcastable batches of vectors (last dim reduced)."""
    diff = a.astype(jnp.float32) - b.astype(jnp.float32)
    return jnp.sum(diff * diff, axis=-1)


def l2_sq_batch(queries: jax.Array, points: jax.Array) -> jax.Array:
    """[Q, d] x [N, d] -> [Q, N] squared distances via the matmul identity."""
    q = queries.astype(jnp.float32)
    x = points.astype(jnp.float32)
    qn = jnp.sum(q * q, axis=-1, keepdims=True)          # [Q, 1]
    xn = jnp.sum(x * x, axis=-1)                          # [N]
    d = qn - 2.0 * jnp.matmul(q, x.T, precision=HIGHEST) + xn[None, :]
    return jnp.maximum(d, 0.0)


def gather_l2(query: jax.Array, vectors: jax.Array, ids: jax.Array) -> jax.Array:
    """Distances from one query to ``vectors[ids]``; invalid ids -> +inf.

    ids: int32 [K] with INVALID padding.  Fetches are clamped so the gather is
    always in-bounds (TPU-friendly), then masked.
    """
    safe = jnp.maximum(ids, 0)
    pts = vectors[safe]                                   # [K, d]
    d = l2_sq(query[None, :], pts)
    return jnp.where(ids >= 0, d, INF)
