#!/usr/bin/env python3
"""Compile a configuration's served programs for a described v5e, without
the chip: the unified search (RW plus five RO lanes and the LTI), the
flush's insert programs, and the merge's Delete-phase repair and
Insert/Patch programs, at the configuration's own widths and capacity.

    JAX_PLATFORMS=cpu python3 bench/tools/compile_rehearsal.py sift1b_shard

Prints one line per program: compiled or the compiler's error, whether a
Mosaic kernel is in it, and the program's device memory.  A compile that
passes is not a chip run; it catches what the chip's compiler refuses.
"""
import dataclasses
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))


def main(name: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from harness.window import system_config
    from repro.core import delete as dl
    from repro.core import index as mem
    from repro.core import pq as pqm
    from repro.core.graph import GraphState, LaneStack
    from repro.core.merge import _insert_patch_phases
    from repro.kernels import ops

    jax.config.update("jax_enable_compilation_cache", False)
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        c = json.load(f)
    ops._interpret = lambda: False
    scfg = system_config(c)
    icfg = dataclasses.replace(scfg.index, use_kernel=True)
    tcfg = dataclasses.replace(icfg, capacity=scfg.temp_capacity)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    cap, d, R, m = c["capacity"], c["dim"], c["R"], c["pq_m"]
    tcap, tt, bq = c["temp_capacity"], 6, c["batch_queries"]

    def graph(lead, n):
        return GraphState(S(lead + (n, d), jnp.float32),
                          S(lead + (n, R), jnp.int32),
                          S(lead + (n,), jnp.bool_), S(lead + (n,), jnp.bool_),
                          S(lead, jnp.int32), S(lead, jnp.int32))

    lti = graph((), cap)
    codes = S((cap, m), jnp.uint8)
    codebook = S((m, c["pq_ksub"], d // m), jnp.float32)
    stack = LaneStack(graph((tt,), tcap), lti, codes, codebook)
    L, k = c["L_search"], c["k"]
    ib, staged, block = c["insert_batch"], c["merge_threshold"], c["merge_block"]
    programs = {
        "unified_search": lambda: mem.unified_search.lower(
            stack, S((tt, tcap), jnp.int32), S((cap,), jnp.int32),
            S((tt, tcap), jnp.bool_), S((cap,), jnp.bool_),
            S((bq, d), jnp.float32), icfg, k=k,
            k_lane=min(max(2 * k, k + 8), L), L=L,
            beam_width=c["beam_width"], rerank=True),
        "flush_edges": lambda: mem.insert_edges_stage.lower(
            graph((), tcap), S((ib,), jnp.int32), S((ib, d), jnp.float32),
            tcfg),
        "flush_delta": lambda: mem.insert_apply_delta.lower(
            graph((), tcap), S((ib * R,), jnp.int32), S((ib * R,), jnp.int32),
            tcfg),
        "merge_decode": lambda: pqm.decode.lower(
            pqm.PQCodebook(codebook), codes, scfg.pq),
        "merge_delete_repair": lambda: dl._repair_blocks_fp.lower(
            lti.adjacency, S((cap, d), jnp.float32), lti.deleted, lti.active,
            S((48, block), jnp.int32), icfg.alpha, R, True),
        "merge_insert_patch": lambda: _insert_patch_phases.lower(
            lti, codes, pqm.PQCodebook(codebook), S((cap, d), jnp.float32),
            S((staged, d), jnp.float32), S((staged,), jnp.bool_),
            S((), jnp.int32), S((), jnp.int32), icfg, scfg.pq,
            insert_chunk=ib, block=block, use_sdc=False),
    }
    failed = 0
    for prog, lower in programs.items():
        try:
            compiled = lower().compile()
        except Exception as e:   # report every program, then fail
            failed += 1
            print(json.dumps({"config": name, "program": prog,
                              "compiled": False,
                              "error": str(e).splitlines()[0][:300]}))
            continue
        mem_a = compiled.memory_analysis()
        print(json.dumps({
            "config": name, "program": prog, "compiled": True,
            "mosaic": "tpu_custom_call" in compiled.as_text(),
            "temp_bytes": getattr(mem_a, "temp_size_in_bytes", None),
            "argument_bytes": getattr(mem_a, "argument_size_in_bytes", None),
        }), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
