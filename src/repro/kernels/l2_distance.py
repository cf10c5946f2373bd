"""Pallas TPU kernel: tiled squared-L2 distance matrix.

Used by exact rerank (the paper's "fetch full-precision vectors and re-rank"),
brute-force ground truth, and k-means assignment during PQ training.

``||q - x||^2 = ||q||^2 - 2 q.x + ||x||^2`` — the cross term is an MXU matmul;
norms are fused into the same kernel so each (query-block, point-block) tile
is computed entirely in VMEM with one HBM read per operand tile.

Grid: (Q / block_q, N / block_n, d / block_d) with accumulation over the
contraction dimension in a VMEM scratch accumulator (classic Pallas matmul
schedule; the d-axis is the innermost, sequential grid dimension).

Contract: ``ref.l2_distances_ref`` (see docs/KERNELS.md); parity enforced
by ``tests/test_kernels.py::test_l2_matches_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _l2_kernel(q_ref, x_ref, out_ref, acc_ref, *, n_dblocks: int):
    d_idx = pl.program_id(2)

    @pl.when(d_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)                 # [BQ, BD]
    x = x_ref[...].astype(jnp.float32)                 # [BN, BD]
    cross = jax.lax.dot_general(
        q, x, dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)            # [BQ, BN]
    qn = jnp.sum(q * q, axis=1, keepdims=True)         # [BQ, 1]
    xn = jnp.sum(x * x, axis=1)[None, :]               # [1, BN]
    acc_ref[...] += qn - 2.0 * cross + xn

    @pl.when(d_idx == n_dblocks - 1)
    def _done():
        out_ref[...] = jnp.maximum(acc_ref[...], 0.0)


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_n", "block_d", "interpret"))
def l2_distances_kernel(queries: jax.Array, points: jax.Array, *,
                        block_q: int = 128, block_n: int = 256,
                        block_d: int = 128,
                        interpret: bool = False) -> jax.Array:
    """queries [Q, d], points [N, d] -> f32 [Q, N] squared distances."""
    Q, d = queries.shape
    N, d2 = points.shape
    assert d == d2
    assert Q % block_q == 0 and N % block_n == 0 and d % block_d == 0
    n_dblocks = d // block_d
    grid = (Q // block_q, N // block_n, n_dblocks)
    return pl.pallas_call(
        functools.partial(_l2_kernel, n_dblocks=n_dblocks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, block_d), lambda q, n, k: (q, k)),
            pl.BlockSpec((block_n, block_d), lambda q, n, k: (n, k)),
        ],
        out_specs=pl.BlockSpec((block_q, block_n), lambda q, n, k: (q, n)),
        out_shape=jax.ShapeDtypeStruct((Q, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_q, block_n), jnp.float32)],
        interpret=interpret,
    )(queries, points)
