"""Distribution layer: sharding rules, dry-run build graph, and true
multi-device behaviour (via a subprocess with 8 placeholder host devices —
tests themselves keep the default 1-device runtime)."""
import json
import os
import subprocess
import sys

import jax
import pytest

from repro.configs import ASSIGNED, get_arch
from repro.distributed.sharding import spec_for
from repro.launch.mesh import make_host_mesh

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_spec_for_divisibility():
    mesh = make_host_mesh()   # (1, 1) mesh: everything divisible
    s = spec_for(mesh, (16, 32), ["data", "model"])
    assert len(s) == 2


def test_every_arch_has_assigned_cells():
    want = {
        "lm": {"train_4k", "prefill_32k", "decode_32k", "long_500k"},
        "gnn": {"full_graph_sm", "minibatch_lg", "ogb_products",
                "molecule"},
        "recsys": {"train_batch", "serve_p99", "serve_bulk",
                   "retrieval_cand"},
    }
    for name in ASSIGNED:
        arch = get_arch(name)
        shapes = {c.shape for c in arch.cells}
        assert shapes == want[arch.family], (name, shapes)


def test_long_500k_skips_documented():
    for name in ("qwen3-14b", "qwen2-1.5b", "qwen3-moe-30b-a3b"):
        assert get_arch(name).cell("long_500k").skip
    for name in ("gemma3-12b", "mixtral-8x7b"):
        assert not get_arch(name).cell("long_500k").skip


def test_input_specs_materialize_without_allocation():
    for name in ASSIGNED:
        arch = get_arch(name)
        for cell in arch.cells:
            specs = cell.specs()
            for k, v in specs.items():
                leaves = jax.tree.leaves(
                    v, is_leaf=lambda x: hasattr(x, "shape"))
                assert leaves, (name, cell.shape, k)


_MULTIDEV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.launch.mesh import mesh_with_auto_axes
mesh = mesh_with_auto_axes((2, 4), ("data", "model"))

# 1) distributed ANN search == single-shard brute force on union of shards
from repro.core.config import IndexConfig, PQConfig
from repro.core.lti import build_lti
from repro.core import pq as pqm
from repro.core.graph import GraphState
from repro.core.lti import LTIState
from repro.launch.ann_steps import make_distributed_search

cfg = IndexConfig(capacity=256, dim=16, R=16, L_build=32, L_search=64,
                  alpha=1.2, max_visits=96)
pq = PQConfig(dim=16, m=8, ksub=32, kmeans_iters=6)
rng = np.random.default_rng(0)
centers = rng.standard_normal((16, 16)).astype(np.float32) * 4.0
shards = []
all_pts = []
for s in range(8):
    which = rng.integers(0, 16, 200)
    pts = (centers[which]
           + rng.standard_normal((200, 16))).astype(np.float32)
    all_pts.append(pts)
    shards.append(build_lti(pts, cfg, pq, seed=s))

def cat(field):
    return jnp.concatenate([getattr(l.graph, field) for l in shards])

g = GraphState(vectors=cat("vectors"), adjacency=cat("adjacency"),
               active=cat("active"), deleted=cat("deleted"),
               start=jnp.stack([l.graph.start for l in shards]),
               n_total=jnp.stack([l.graph.n_total for l in shards]))
lti = LTIState(g, jnp.concatenate([l.codes for l in shards]),
               shards[0].codebook)  # shared codebook approx: re-encode
codes = []
for s, l in enumerate(shards):
    c = pqm.encode(shards[0].codebook, jnp.asarray(all_pts[s]), pq)
    full = jnp.zeros((cfg.capacity, pq.m), jnp.uint8).at[:200].set(c)
    codes.append(full)
lti = LTIState(g, jnp.concatenate(codes), shards[0].codebook)

search = make_distributed_search(mesh, cfg, k=5)
# queries = perturbed dataset points from several shards
union0 = np.concatenate(all_pts)
q = (union0[rng.choice(1600, 8, replace=False)]
     + 0.05 * rng.standard_normal((8, 16))).astype(np.float32)
with mesh:
    ids, d = search(lti, jnp.asarray(q))
ids = np.asarray(ids)

# ground truth over the union
union = np.concatenate(all_pts)
slot_of = np.concatenate([np.arange(200) + s * cfg.capacity
                          for s in range(8)])
dist = ((union[None] - q[:, None]) ** 2).sum(-1)
gt = slot_of[np.argsort(dist, axis=1)[:, :5]]
inter = [len(set(ids[i].tolist()) & set(gt[i].tolist())) / 5
         for i in range(8)]
recall = float(np.mean(inter))

# 2) elastic checkpoint resharding: save on 1 device, restore onto 8
from repro.checkpoint.store import save_checkpoint, restore_checkpoint
tree = {"w": np.arange(64, dtype=np.float32).reshape(8, 8)}
save_checkpoint("/tmp/_elastic_ck", 1, tree)
sh = {"w": NamedSharding(mesh, P("data", "model"))}
got, _ = restore_checkpoint("/tmp/_elastic_ck", shardings=sh)
ok_shard = (len(got["w"].sharding.device_set) == 8
            and np.allclose(np.asarray(got["w"]), tree["w"]))

# 3) int8 compressed all-reduce inside shard_map
from repro.optim.compress import int8_all_gather_reduce, bf16_all_reduce
from functools import partial
x = np.linspace(-1, 1, 8 * 32).astype(np.float32).reshape(8, 32)

def red(xs, key):
    return int8_all_gather_reduce({"g": xs}, key, "data")["g"]

out = jax.jit(jax.shard_map(
    partial(red, key=jax.random.PRNGKey(0)),
    mesh=Mesh(np.array(jax.devices()).reshape(8), ("data",)),
    in_specs=P("data"), out_specs=P("data")))(x.reshape(8, 32))
want = np.broadcast_to(x.reshape(8, 32).mean(0, keepdims=True), (8, 32))
err = float(np.abs(np.asarray(out).reshape(8, 32) - want).max())

print(json.dumps({"recall": recall, "elastic_ok": bool(ok_shard),
                  "int8_err": err}))
"""


@pytest.fixture(scope="module")
def multidev_result():
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", _MULTIDEV_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_distributed_ann_search_recall(multidev_result):
    assert multidev_result["recall"] >= 0.8, multidev_result


def test_elastic_checkpoint_restore(multidev_result):
    assert multidev_result["elastic_ok"]


def test_int8_allreduce_accuracy(multidev_result):
    # stochastic-rounding int8: error bounded by the quantization step
    assert multidev_result["int8_err"] < 0.02, multidev_result
