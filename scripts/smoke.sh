#!/usr/bin/env bash
# CI smoke: docs reference check + tier-1 tests + a short kernel-path
# throughput probe.
#
# JAX_PLATFORMS=cpu keeps every step on the CPU, where the Pallas kernels run
# through the interpreter, so kernel-path regressions (shape/padding/
# semantics) surface on any box without a TPU.  The bench probe builds a small LTI and runs the beam-width
# sweep with the kernels enabled — ~30s end to end.
#
# `smoke.sh --shards` runs the sharded-serving probe instead: 4 fake host
# devices (XLA_FLAGS) + scripts/shard_probe.py asserting the shard-count
# invariance / dispatch / micro-batching contracts of docs/SERVING.md.
#
# `smoke.sh --serving` runs the serving-front-end probe instead: 4 fake host
# devices + scripts/serving_probe.py asserting the continuous-batching
# scheduler's virtual-clock invariants (deadline-aware close, backpressure
# shed, scheduled-vs-direct bit-parity) and multi-replica routing (1/2/4
# replica parity, 2x2 replica-x-shard composition, round-robin accounting,
# background-merge survival) — contracts of docs/SERVING.md.
#
# `smoke.sh --disk` runs the storage-tier probe instead: a tiny system with
# storage_dir set + scripts/disk_probe.py asserting bit-parity at prefetch
# depths 0/1/2, the read/cache-hit conservation law, delta patching, and
# staging-buffer reuse (contracts of docs/STORAGE.md).
#
# `smoke.sh --locality` runs the locality-aware update batching probe
# instead: two systems differing only in SystemConfig.locality_order driven
# through the same clustered stream + scripts/locality_probe.py asserting
# seeded-permutation determinism, bucketed prune-launch reduction, storage
# delta coherence, and recall equivalence (contracts of
# docs/ARCHITECTURE.md, "Update-path locality").
#
# `smoke.sh --filters` runs the filtered/multi-tenant probe instead: 4 fake
# host devices + scripts/filter_probe.py asserting selectivity-1.0 bit-parity
# (direct + replica-routed), tenant isolation across tiers, post-merge label
# survival, and the scheduler's single-spec batch closes + tenant-quota
# sheds (contracts of docs/ARCHITECTURE.md, "Filtered & multi-tenant
# search").
#
# `smoke.sh --local-repair` runs the localized delete-repair probe instead:
# two systems routed always-local vs always-global through interleaved
# inserts/deletes/merges + scripts/local_repair_probe.py asserting merge
# bit-parity across the routing, the repair counters, the reachability
# gauge, and standalone consolidate() (contracts of docs/ARCHITECTURE.md,
# "Localized delete repair").
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}
export JAX_PLATFORMS=cpu

if [[ "${1:-}" == "--shards" ]]; then
  XLA_FLAGS="--xla_force_host_platform_device_count=4" \
    python scripts/shard_probe.py
  exit 0
fi

if [[ "${1:-}" == "--serving" ]]; then
  XLA_FLAGS="--xla_force_host_platform_device_count=4" \
    python scripts/serving_probe.py
  exit 0
fi

if [[ "${1:-}" == "--filters" ]]; then
  XLA_FLAGS="--xla_force_host_platform_device_count=4" \
    python scripts/filter_probe.py
  exit 0
fi

if [[ "${1:-}" == "--disk" ]]; then
  python scripts/disk_probe.py
  exit 0
fi

if [[ "${1:-}" == "--local-repair" ]]; then
  python scripts/local_repair_probe.py
  exit 0
fi

if [[ "${1:-}" == "--locality" ]]; then
  python scripts/locality_probe.py
  exit 0
fi

# Docs first (cheapest): docs/*.md + README references (file paths, links,
# file.py::symbol refs, python snippets) must match the tree.
python scripts/check_docs.py

# Kernel probe next: surfaces kernel-path regressions even when an
# unrelated (e.g. env-dependent) test failure would abort the -x suite run.
python - <<'PY'
import time
import jax.numpy as jnp
import numpy as np

from benchmarks.common import dataset, default_pq, queryset
from benchmarks.bench_throughput import beam_sweep
from repro.core.config import IndexConfig
from repro.core.lti import build_lti

t0 = time.time()
n, dim = 600, 32
cfg = IndexConfig(capacity=2 * n, dim=dim, R=20, L_build=24, L_search=32,
                  alpha=1.2, use_kernel=True)   # force the Pallas ops path
lti = build_lti(dataset(n, dim), cfg, default_pq(dim), batch=64)
beam_sweep(lti, cfg, queryset(16, dim), widths=(1, 4), tag="smoke_beam")

# Fused frontier_select: Pallas (interpret) must match the jnp contract
# bit-for-bit on an engine-shaped input, including INVALID-padded lanes.
from repro.kernels import ops
rng = np.random.default_rng(0)
L, K, V, W = 16, 24, 30, 4
cand_i = jnp.asarray(np.concatenate([rng.permutation(100)[:8],
                                     np.full(L - 8, -1)]).astype(np.int32))
cand_d = jnp.asarray(np.concatenate([np.sort(rng.random(8)),
                                     np.full(L - 8, np.inf)]).astype(np.float32))
new_i = jnp.asarray(np.concatenate([200 + rng.permutation(100)[:12],
                                    np.full(K - 12, -1)]).astype(np.int32))
new_d = jnp.asarray(np.concatenate([rng.random(12),
                                    np.full(K - 12, np.inf)]).astype(np.float32))
vis_i = jnp.full((V,), -1, jnp.int32).at[0].set(cand_i[0])
vis_d = jnp.full((V,), jnp.inf, jnp.float32).at[0].set(cand_d[0])
a = ops.frontier_select(cand_i, cand_d, new_i, new_d, vis_i, vis_d,
                        jnp.int32(1), W=W, max_visits=V, use_kernel=True)
b = ops.frontier_select(cand_i, cand_d, new_i, new_d, vis_i, vis_d,
                        jnp.int32(1), W=W, max_visits=V, use_kernel=False)
for x, y in zip(a, b):
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

# Mutation engine: fused robust_prune (Pallas interpret) must match the jnp
# oracle bit-for-bit on an engine-shaped candidate row.
C, d, Rp = 48, 16, 8
vecs = jnp.asarray(rng.standard_normal((C, d)).astype(np.float32))
ids = jnp.asarray(rng.permutation(1000)[:C].astype(np.int32))
ok = jnp.asarray(rng.random(C) > 0.3)
anchor = jnp.asarray(rng.standard_normal(d).astype(np.float32))
diff = anchor[None] - vecs
d_p = jnp.sum(diff * diff, -1)
pw = ops.robust_prune_fp(d_p[None], vecs[None], ids[None], ok[None],
                         alpha=1.2, R=Rp, use_kernel=False)
pg = ops.robust_prune_fp(d_p[None], vecs[None], ids[None], ok[None],
                         alpha=1.2, R=Rp, use_kernel=True)
np.testing.assert_array_equal(np.asarray(pw[0]), np.asarray(pg[0]))
np.testing.assert_array_equal(np.asarray(pw[1]), np.asarray(pg[1]))
print(f"# kernel-path smoke ok in {time.time() - t0:.1f}s")
PY

python -m pytest -x -q
