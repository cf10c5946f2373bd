"""Seeded vector data for the benchmark's configurations.

Points are drawn from a Gaussian mixture whose components each have a
decaying spectrum in a random basis of their own: component ``c`` has a
centre ``mu_c`` and a noise ``U_c @ (s * z)`` with ``s_j = noise_scale *
exp(-j / spectrum_decay)``.  Isotropic noise in 128 dimensions puts all of
a query's neighbours at nearly one distance, so 32-byte PQ codes cannot
rank them (an exhaustive PQ scan plus exact rerank reached 5-recall@5 0.70
at 2^17 points on such data); a decaying spectrum gives each component a
low intrinsic dimension, as real descriptors have, and PQ can then separate
neighbours.

Everything is a pure function of ``(seed, stream)``: the same seed gives
the same points, and every seed gives the same sizes.  A configuration
whose ``data`` names a ``geometry_seed`` replays one fixed data set: its
centres and bases come from that seed, and the run's seed draws only the
points, queries and arrivals.
"""
from __future__ import annotations

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per (seed, stream); any whole seed, signed or
    wider than 32 bits, is accepted."""
    return np.random.default_rng(
        np.random.SeedSequence([abs(int(seed)), int(seed < 0), stream]))


class Mixture:
    """The mixture of one configuration under one seed."""

    def __init__(self, dim: int, components: int, center_scale: float,
                 noise_scale: float, spectrum_decay: float, seed: int):
        r = rng_for(seed, 0)       # the geometry's own stream
        self.dim = dim
        self.components = components
        self.centers = r.standard_normal((components, dim)) * center_scale
        self.scales = noise_scale * np.exp(-np.arange(dim) / spectrum_decay)
        # One orthonormal basis per component (QR of a Gaussian matrix).
        g = r.standard_normal((components, dim, dim))
        self.bases = np.linalg.qr(g)[0]

    @classmethod
    def from_config(cls, config: dict, seed: int) -> "Mixture":
        d = config["data"]
        return cls(config["dim"], d["components"], d["center_scale"],
                   d["noise_scale"], d["spectrum_decay"],
                   d.get("geometry_seed", seed))

    def sample(self, which: np.ndarray, r: np.random.Generator) -> np.ndarray:
        """One float32 point per entry of ``which`` (component ids)."""
        out = np.empty((len(which), self.dim), np.float32)
        z = r.standard_normal((len(which), self.dim)) * self.scales
        for c in np.unique(which):
            rows = np.nonzero(which == c)[0]
            out[rows] = self.centers[c] + z[rows] @ self.bases[c].T
        return out


def components_for(n: int, components: int, order: str,
                   cluster_points: int, r: np.random.Generator) -> np.ndarray:
    """Component id of each of ``n`` points in arrival order.

    ``shuffled``: i.i.d. uniform over the components.  ``clustered``: the
    points arrive one component at a time, ``cluster_points`` per component
    (the Big-ANN clustered runbook)."""
    if order == "shuffled":
        return r.integers(0, components, n)
    if order == "clustered":
        return (np.arange(n) // cluster_points) % components
    raise ValueError(f"unknown arrival order {order!r}")
