"""Pallas TPU kernel: PQ asymmetric distance computation (ADC).

The hot op of the paper's LTI: every beam-search step scores candidate nodes
from their ~32-byte PQ codes against a per-query lookup table,
``out[q, n] = sum_m lut[q, m, codes[n, m]]``.

TPU adaptation (the paper's CPU idiom is a scalar gather loop; TPUs hate
scalar gathers): re-associate the LUT gather as a *one-hot matmul*,

    onehot(codes)  [BN, m*ksub]  @  lut_flat.T  [m*ksub, BQ]  ->  [BN, BQ]

which lands on the MXU.  The one-hot tensor is never materialized in HBM —
it is built in VMEM per (code-block x query-block) grid cell from an iota
comparison, so HBM traffic is exactly codes (1 byte/entry) + LUTs + outputs.

Grid: (N / block_n, Q / block_q); each cell reads a [block_n, m] uint8 code
block and a [block_q, m, ksub] LUT block, both VMEM-resident.

Contract: ``ref.adc_distances_ref`` (see docs/KERNELS.md); parity enforced
by ``tests/test_kernels.py::test_adc_matches_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _adc_kernel(codes_ref, lut_ref, out_ref, *, ksub: int):
    codes = codes_ref[...].astype(jnp.int32)          # [BN, m]
    lut = lut_ref[...].astype(jnp.float32)            # [BQ, m, ksub]
    bn, m = codes.shape
    bq = lut.shape[0]
    # one-hot over the fused (m, ksub) axis: onehot[n, m, k] = codes[n,m]==k
    iota = jax.lax.broadcasted_iota(jnp.int32, (bn, m, ksub), 2)
    onehot = (codes[:, :, None] == iota).astype(jnp.float32)
    onehot2 = onehot.reshape(bn, m * ksub)
    lut2 = lut.reshape(bq, m * ksub)
    acc = jax.lax.dot_general(
        onehot2, lut2,
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)            # [BN, BQ]
    out_ref[...] = acc.T                               # [BQ, BN]


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_q", "interpret"))
def adc_distances_kernel(codes: jax.Array, luts: jax.Array, *,
                         block_n: int = 128, block_q: int = 8,
                         interpret: bool = False) -> jax.Array:
    """codes uint8 [N, m], luts f32 [Q, m, ksub] -> f32 [Q, N].

    N and Q are padded to block multiples by the caller (``ops.py``).
    """
    N, m = codes.shape
    Q, m2, ksub = luts.shape
    assert m == m2, (m, m2)
    assert N % block_n == 0 and Q % block_q == 0, (N, Q, block_n, block_q)
    grid = (Q // block_q, N // block_n)
    return pl.pallas_call(
        functools.partial(_adc_kernel, ksub=ksub),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, m), lambda q, n: (n, 0)),
            pl.BlockSpec((block_q, m, ksub), lambda q, n: (q, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, block_n), lambda q, n: (q, n)),
        out_shape=jax.ShapeDtypeStruct((Q, N), jnp.float32),
        interpret=interpret,
    )(codes, luts)
