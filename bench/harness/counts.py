"""Operations and bytes each kernel launch needs, from its shapes.

Each function takes the launch's operand shapes as the trace writes them
(``trace.operand_shapes``: the result first, then the operands) and returns
``(flops, bytes)``; ``roofline_seconds`` says which peak bounds them.  Only what the algorithm needs is counted: padded
lanes and recomputation are not, so a share of the roofline can only be
understated, never pushed past 100% by the count.
"""
from __future__ import annotations

import math

def roofline_seconds(flops: float, nbytes_: float, peaks: dict) -> tuple:
    """Least time at the chip's peaks, and which of the two bounds it."""
    t_c = flops / peaks["flops_bf16"]
    t_m = nbytes_ / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def pq_adc(shapes: list, *, m: int, ksub: int) -> tuple:
    """ADC distances of n candidates for one query per batch row, from
    [n, m] uint8 codes and one [m, ksub] float32 table: one lookup and one
    add per code entry; bytes are the codes, the table and the n results,
    each read or written once.  Memory-bound: ~1 flop per byte.

    ``shapes`` is the launch as the trace writes it: result [B, bq, n],
    codes [B, n, m], tables [B, bq, m, ksub].  ``bq`` is the kernel's block
    of queries, padded from the one query each row of a vmapped beam search
    scores, so one table per row is counted."""
    out, codes = shapes[0], shapes[1]
    *lead, _, n = out[1]
    if codes[1][-2:] != (n, m):
        raise ValueError(f"not a pq_adc launch at m={m}: {shapes[:3]}")
    rows = math.prod(lead)
    flops = 2.0 * rows * n * m
    need = rows * (n * m + m * ksub * 4 + n * 4)
    return flops, float(need)


def robust_prune(shapes: list, *, R: int) -> tuple:
    """R rounds of RobustPrune over B rows of C candidates each, with d-wide
    float32 candidate vectors: per round, the winner's distance to every
    candidate (3 d flops each) and the alpha-coverage compare (2 flops).
    Bytes: the [B, C] distances and ids and the [B, C, d] vectors read once,
    the [B, R] result written once.  Memory-bound: ~0.75 R flops per byte,
    under the v5e's 240.

    ``shapes`` is (result ids [.., G, Rpad], counts, d_p [.., G, C],
    vectors [.., G, d, C], ids [.., G, C]) as the fp flavour launches it."""
    d_p, vecs = shapes[2], shapes[3]
    *lead, g, c = d_p[1]
    if vecs[1][-1] != c or len(vecs[1]) != len(d_p[1]) + 1:
        raise ValueError(f"not an fp prune launch: {shapes[:5]}")
    rows = math.prod(lead) * g
    d = vecs[1][-2]
    flops = float(rows) * R * c * (3 * d + 2)
    need = rows * (c * 4 + c * 4 + c * d * 4 + R * 4)
    return flops, float(need)
