"""Pallas TPU kernels for the FreshDiskANN compute hot-spots.

Three kernels, each with a pure-jnp oracle in ``ref.py`` and a jit'd public
wrapper in ``ops.py`` (which runs them in interpret mode exactly when the
backend is the CPU):

  pq_adc       — asymmetric distance computation over PQ codes.  The paper's
                 single hottest op: every navigation step of the SSD/LTI index
                 scores R neighbors from their 32-byte codes.  TPU adaptation:
                 instead of scalar table lookups (SSD/CPU idiom), the LUT
                 gather is re-associated as one-hot(codes) @ LUT — an MXU
                 matmul — tiled so codes stream HBM->VMEM block-by-block.
  l2_distance  — tiled ||q - x||^2 via the matmul identity (rerank + brute
                 force ground truth + k-means assignment).
  block_topk   — streaming block top-k merge (candidate-list maintenance of
                 Algorithm 1 / final result aggregation across shards).

The *mutation* hot path (insert / delete-repair / StreamingMerge) adds two
fused kernels with the same ref/ops/parity structure:

  robust_prune — Algorithm 3's R sequential selection rounds (masked argmin
                 + winner coverage row + alpha-mask update) in ONE launch
                 per node, full-precision and SDC-code flavors; vmapped
                 over node blocks by ``core.prune.robust_prune_batch``.
  delete_repair— Algorithm 4's per-node repair step (neighbor-of-deleted-
                 neighbor candidate assembly + prune rounds + changed-row
                 select) in one launch; drives
                 ``core.delete.consolidate_deletes`` and the StreamingMerge
                 delete phase.

These wrappers ARE the search hot path: the beam-width engine in
``repro.core.search`` routes every iteration through them when
``use_kernel`` resolves true (``IndexConfig.use_kernel``; None -> auto-on
for TPU backends).  A ``DistanceBackend`` (``FullPrecisionBackend`` /
``PQBackend``) gathers the beam's W x R neighbor rows and scores them with
one ``l2_distances`` / ``adc_distances`` call on a padded fixed-shape batch,
and the candidate list is maintained with one ``block_topk`` merge per
round.  With ``use_kernel=False`` the engine runs the bit-identical jnp
reference path — the parity tests in ``tests/test_beam_search.py`` toggle
the flag both ways.
"""
from .ops import (adc_distances, l2_distances, block_topk,  # noqa: F401
                  robust_prune_fp, robust_prune_sdc,
                  delete_repair_fp, delete_repair_sdc)
