"""Product Quantization (Jegou et al. [35]) — codebook training, encode,
decode, LUT construction and asymmetric distance computation (ADC).

The LTI stores only PQ codes in fast memory (paper §5: B = 32 bytes/vector);
every StreamingMerge distance and every LTI navigation distance is an ADC
against a per-query lookup table.  ``repro.kernels.pq_adc`` provides the
Pallas TPU kernel for the ADC hot loop; this module is the reference path and
the codebook machinery.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .config import PQConfig
from .distance import HIGHEST


class PQCodebook(NamedTuple):
    centroids: jax.Array   # [m, ksub, dsub] float32


def _assign(x: jax.Array, cent: jax.Array) -> jax.Array:
    """x [N, dim], cent [m, ksub, dsub] -> codes [N, m] int32.

    Squared distances are summed from the differences, one dsub component
    at a time (``x[:, t::dsub]`` is component t of every subvector), in
    plain f32 elementwise arithmetic.  No matmul: on the v5e the
    ``||c||^2 - 2 x.c`` form at HIGHEST precision came out wrong (as an
    einsum and as a 2-D matmul alike; codes matched the CPU's on 13-25% of
    entries), and at default precision it rounds x to bf16."""
    m, ksub, dsub = cent.shape
    d2 = jnp.zeros((x.shape[0], m, ksub), jnp.float32)
    for t in range(dsub):
        diff = x[:, t::dsub, None] - cent[None, :, :, t]    # [N, m, ksub]
        d2 = d2 + diff * diff
    return jnp.argmin(d2, axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("cfg",))
def train_pq(data: jax.Array, cfg: PQConfig) -> PQCodebook:
    """Lloyd's k-means per subspace (vectorised across all m subspaces)."""
    n = data.shape[0]
    x = data.astype(jnp.float32)                            # [N, dim]
    key = jax.random.PRNGKey(cfg.seed)
    init_idx = jax.random.choice(key, n, (cfg.ksub,), replace=n < cfg.ksub)
    cent = jnp.transpose(x[init_idx].reshape(cfg.ksub, cfg.m, cfg.dsub),
                         (1, 0, 2))                         # [m, ksub, dsub]

    def step(cent, _):
        codes = _assign(x, cent)                            # [N, m]
        oh = jax.nn.one_hot(codes, cfg.ksub, dtype=jnp.float32)  # [N, m, k]
        # Per-centroid sums, one dsub component at a time, as f32 sums of
        # one-hot-masked values (no matmul; see _assign).
        sums = jnp.stack([jnp.sum(oh * x[:, t::cfg.dsub, None], axis=0)
                          for t in range(cfg.dsub)], axis=-1)  # [m, k, dsub]
        cnts = jnp.sum(oh, axis=0)                          # [m, k]
        new = sums / jnp.maximum(cnts, 1.0)[..., None]
        cent = jnp.where((cnts > 0)[..., None], new, cent)  # keep empty as-is
        return cent, None

    cent, _ = jax.lax.scan(step, cent, None, length=cfg.kmeans_iters)
    return PQCodebook(cent)


@functools.partial(jax.jit, static_argnames=("cfg",))
def encode(codebook: PQCodebook, data: jax.Array, cfg: PQConfig) -> jax.Array:
    """Vectors -> uint8 codes [N, m]."""
    x = data.astype(jnp.float32)
    return _assign(x, codebook.centroids).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("cfg",))
def decode(codebook: PQCodebook, codes: jax.Array, cfg: PQConfig) -> jax.Array:
    """Codes -> reconstructed vectors [N, dim] (used for prune distances).

    Output column j is centroid element ``[j // dsub, code, j % dsub]``:
    one gather from the flattened codebook straight into the [N, dim]
    layout.  No [N, m, dsub] intermediate: on a TPU its dsub-wide minor
    axis pads to 128 lanes, a 32x blow-up (32 GiB at a 2M-row LTI).  The
    per-column code is the [N, m] codes times a 0/1 expansion matrix
    (exact: one nonzero term, integers below 2^8)."""
    m, ksub, dsub = codebook.centroids.shape
    j = jnp.arange(m * dsub)
    expand = (j[None, :] // dsub == jnp.arange(m)[:, None]).astype(
        jnp.float32)                                        # [m, dim]
    c = jnp.matmul(codes.astype(jnp.float32), expand,
                   precision=HIGHEST).astype(jnp.int32)     # [N, dim]
    flat = codebook.centroids.reshape(-1)
    return flat[(j // dsub * ksub + c) * dsub + j % dsub]


def lut(codebook: PQCodebook, query: jax.Array) -> jax.Array:
    """Per-query ADC lookup table [m, ksub] of squared subspace distances."""
    m, ksub, dsub = codebook.centroids.shape
    q = query.astype(jnp.float32).reshape(m, 1, dsub)
    diff = q - codebook.centroids
    return jnp.sum(diff * diff, axis=-1)                    # [m, ksub]


def adc(codes: jax.Array, table: jax.Array) -> jax.Array:
    """ADC: sum_m table[m, codes[:, m]] -> [N] approximate squared distances.

    Reference (jnp) path; the Pallas kernel computes the same contraction as a
    one-hot matmul on the MXU.
    """
    c = codes.astype(jnp.int32)                             # [N, m]
    m = table.shape[0]
    gathered = table[jnp.arange(m)[None, :], c]             # [N, m]
    return jnp.sum(gathered, axis=-1)


def adc_gather(codes: jax.Array, table: jax.Array, ids: jax.Array) -> jax.Array:
    """ADC for a subset of rows; INVALID ids -> +inf (search dist_fn shape)."""
    safe = jnp.maximum(ids, 0)
    d = adc(codes[safe], table)
    return jnp.where(ids >= 0, d, jnp.inf)


# ---------------------------------------------------------------------------
# SDC — symmetric distance computation between two PQ codes.
#
# sdc(a, b) == ||decode(a) - decode(b)||^2 exactly (the squared distance
# decomposes per subspace), but reads 1 byte/subspace per point instead of
# dsub*4 — this is what makes StreamingMerge's prune passes touch 16x fewer
# bytes than decoding vectors (the paper's "use the compressed PQ vectors
# for approximate distances", taken to its traffic-optimal form).
# ---------------------------------------------------------------------------

def sdc_tables(codebook: PQCodebook) -> jax.Array:
    """Centroid-pair squared distances [m, ksub, ksub] (~8MB for 32x256)."""
    c = codebook.centroids
    diff = c[:, :, None, :] - c[:, None, :, :]
    return jnp.sum(diff * diff, axis=-1)


def sdc_lut(tables: jax.Array, code: jax.Array) -> jax.Array:
    """Anchor one code: returns an ADC-shaped LUT [m, ksub] so that
    ``adc(codes_b, sdc_lut(tables, a)) == sdc(a, b)`` for every b."""
    m = tables.shape[0]
    return tables[jnp.arange(m), code.astype(jnp.int32)]
