"""Every main-path Pallas kernel compiles for a TPU v5e at the deployment's
widths (``configs/freshdiskann_1b.py::FULL``: R=64, L=100, W=4, PQ 32x256,
d=128, delete-repair candidates C = R + R^2 padded to 128).

No chip is needed: the TPU compiler is installed, and it compiles for a
described ``v5e:2x2`` topology that is not attached.  The interpreter
accepts kernels Mosaic refuses (unaligned blocks, gathers, dynamic slices,
i1 loop carries), so these compiles are what guard the chip path on a CPU
box.  The topology is described inside a fixture — never at import — so
only the test worker that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import pq as pqm
from repro.core.config import PQConfig
from repro.kernels.delete_repair import (delete_repair_fp_kernel,
                                         delete_repair_sdc_kernel)
from repro.kernels.frontier_select import frontier_select_kernel
from repro.kernels.l2_distance import l2_distances_kernel
from repro.kernels.pq_adc import adc_distances_kernel
from repro.kernels.robust_prune import (robust_prune_fp_kernel,
                                        robust_prune_sdc_kernel)

R, L, W, M_SUB, KSUB, D = 64, 100, 4, 32, 256, 128
VISITS = L + L // 2 + 16                  # IndexConfig.visits_bound(L)
C = -(-(R + R * R) // 128) * 128          # 4224
B = 64                                    # query batch / block rows
CAP = 2_097_152                           # the per-chip shard's capacity


def _pad128(n):
    return -(-n // 128) * 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A compile for a described chip cannot be read back from the
        # persistent cache without the chip: keep the cache out of it.
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def topo4_mesh(topo):
    """A 1-axis mesh over the four chips of the described v5e:2x2 host."""
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.array(topo.devices).reshape(4), ("data",))


def _frontier(S):
    M = _pad128(L + W * R)
    Vp = _pad128(VISITS)
    fn = lambda a, b, c, d: frontier_select_kernel(
        a, b, c, d, L=L, W=W, max_visits=VISITS)
    return fn, (S((B, M)), S((B, M), jnp.int32), S((B, Vp), jnp.int32),
                S((B, Vp)))


def _pq_adc(S):
    # One query lane of the LTI's ADC step: W*R codes vs a padded LUT block.
    fn = lambda c, t: adc_distances_kernel(c, t, block_n=128, block_q=8)
    return fn, (S((W * R, M_SUB), jnp.uint8), S((8, M_SUB, KSUB)))


def _l2(S):
    fn = lambda q, x: l2_distances_kernel(q, x, block_q=8, block_n=256,
                                          block_d=D)
    return fn, (S((8, D)), S((W * R, D)))


def _prune_fp(S):
    fn = lambda dp, v, i: robust_prune_fp_kernel(dp, v, i, alpha=1.2, R=R)
    return fn, (S((B, C)), S((B, C, D)), S((B, C), jnp.int32))


def _prune_sdc(S):
    fn = lambda dp, c, t, i: robust_prune_sdc_kernel(dp, c, t, i, alpha=1.2,
                                                     R=R)
    return fn, (S((B, C)), S((B, C, M_SUB), jnp.int32),
                S((M_SUB, KSUB, KSUB)), S((B, C), jnp.int32))


def _repair_operands(S, payload):
    i32 = jnp.int32
    return (S((B, R), i32), S((B, R), i32), S((B, C - R), i32),
            S((B, C - R), i32), S((B, C), i32), S((B, C))) + payload + (
            S((B, 1), i32), S((B, 1), i32))


def _repair_fp(S):
    fn = lambda *a: delete_repair_fp_kernel(*a, alpha=1.2, R=R)
    return fn, _repair_operands(S, (S((B, C, D)),))


def _repair_sdc(S):
    fn = lambda *a: delete_repair_sdc_kernel(*a, alpha=1.2, R=R)
    return fn, _repair_operands(S, (S((B, C, M_SUB), jnp.int32),
                                    S((M_SUB, KSUB, KSUB))))


@pytest.mark.parametrize("case", [
    _frontier, _pq_adc, _l2, _prune_fp, _prune_sdc, _repair_fp, _repair_sdc,
], ids=["frontier_select", "pq_adc", "l2_distance", "robust_prune_fp",
        "robust_prune_sdc", "delete_repair_fp", "delete_repair_sdc"])
def test_kernel_compiles_for_v5e(one_chip, case):
    def S(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = case(S)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pq_decode_fits_v5e_at_shard_capacity(one_chip):
    """Decoding every code row of a full shard (the StreamingMerge Delete
    phase's prune table) fits the chip: an [N, m, dsub] intermediate would
    pad its 4-wide minor axis to 128 lanes (32 GiB here, twice the HBM)."""
    def S(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cfg = PQConfig(dim=D, m=M_SUB, ksub=KSUB)
    compiled = pqm.decode.lower(
        pqm.PQCodebook(S((M_SUB, KSUB, D // M_SUB))),
        S((CAP, M_SUB), jnp.uint8), cfg=cfg).compile()
    mem = compiled.memory_analysis()
    out_bytes = CAP * D * 4
    assert mem.output_size_in_bytes == out_bytes
    assert mem.temp_size_in_bytes <= 2 * out_bytes


def test_sharded_search_step_compiles_for_four_v5e(topo4_mesh, monkeypatch):
    """The unified search step with the LTI lane sharded over four chips
    (``shard_lti=4``) compiles with the kernels on.  Every Mosaic kernel
    must sit inside a ``shard_map``: XLA cannot partition one itself, and
    the temp lanes' kernels once sat outside it."""
    import dataclasses

    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.config import IndexConfig, SystemConfig
    from repro.core.graph import LaneStack
    from repro.core.system import bootstrap_system
    from repro.data.pipelines import vector_stream
    from repro.distributed.sharding import lti_lane_specs
    from repro.kernels import ops
    from repro.serving.steps import make_sharded_unified_step

    idx = IndexConfig(capacity=1024, dim=D, R=R, L_build=75, L_search=L,
                      alpha=1.2, beam_width=W, use_kernel=False)
    cfg = SystemConfig(index=idx, pq=PQConfig(dim=D, m=M_SUB, ksub=KSUB),
                       ro_snapshot_points=128, temp_capacity=256,
                       insert_batch=64, merge_threshold=1 << 30)
    data = next(vector_stream(700, D, seed=0))
    sys_ = bootstrap_system(data[:400], np.arange(400), cfg)
    for i in range(200):
        sys_.insert(400 + i, data[400 + i])
    sys_.search_batch(data[600:608], 5)              # flush into the tiers
    key, stack, t_tabs, l_tab, tables_np, _ = sys_._lane_bundle(
        *sys_._capture_lanes())
    t_drop, l_drop = sys_._drop_mask(key, tables_np)

    mesh = topo4_mesh
    rep = NamedSharding(mesh, P())

    def S(x, sharding=rep):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    gspecs, cspec = lti_lane_specs("data")
    sstack = LaneStack(
        jax.tree.map(S, stack.temps),
        type(stack.lti)(*[S(x, NamedSharding(mesh, sp))
                          for x, sp in zip(stack.lti, gspecs)]),
        S(stack.codes, NamedSharding(mesh, cspec)), S(stack.codebook))
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    step = make_sharded_unified_step(
        mesh, dataclasses.replace(idx, use_kernel=True), k=5, k_lane=13,
        L=L, beam_width=W, rerank=True)
    args = [jax.tree.map(S, a) for a in (t_tabs, l_tab, t_drop, l_drop)]
    compiled = step.lower(sstack, *args,
                          S(jnp.zeros((B, D), jnp.float32))).compile()
    assert "tpu_custom_call" in compiled.as_text()
