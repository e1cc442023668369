#!/usr/bin/env python3
"""Compile a cell's programs at full width for a described TPU v5e, with
no chip: the compiler refuses here what it would refuse there, and
``memory_analysis`` gives each program's device memory.

    JAX_PLATFORMS=cpu python3 bench/compile_only.py qwen3-14b.chat [--all]

Without ``--all`` it compiles the largest decode program and the largest
prefill program of the cell's reachable set (seed 0); with it, every one.
Prints, per program, its lower and compile seconds and its memory, and
the arithmetic of what the pool leaves free.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

GiB = 1 << 30


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import repro.kernels.paged_attention.ops as pa_ops
    import repro.kernels.quant_matmul.ops as qmm_ops
    from harness import programs, spec, traffic, weights
    from repro.serve import CachedDecoder

    # trace the Pallas kernels, as on the chip, not the CPU's jnp path
    pa_ops.on_tpu = qmm_ops.on_tpu = lambda: True
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    c = spec.cell(args.workload)
    m, geo = c["model"], c["cell"]
    a = spec.arch(c["workload"]["config"], m)
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=chip)
    abstract = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                            weights.abstract_arrays(m))
    prog = weights.to_program(abstract, a)
    adapter = CachedDecoder(cfg=a, embed=prog["embed"],
                            final_norm=prog["final_norm"],
                            blocks=prog["blocks"])
    pool_shape = (m["n_layers"], geo["n_pages"], m["n_kv_heads"],
                  geo["page_size"], m["head_dim"])
    pool = (sds(pool_shape, jnp.bfloat16), sds(pool_shape, jnp.bfloat16),
            None, None)
    specs = traffic.generate(c["mix"], geo, m["vocab"], 0, geo["requests"])
    traffic.stagger(specs, geo["in_flight"], 0, m["vocab"])
    progs = programs.reachable(geo, specs, geo["warm_s"] + 60)
    print(f"{args.workload}: {len(progs)} programs: "
          f"{' '.join(p.name for p in progs)}", flush=True)
    if not args.all:
        progs = [max((p for p in progs if p.kind == k),
                     key=lambda p: (p.lanes, p.pages))
                 for k in ("decode", "prefill")]
    w_bytes = sum(x.size * x.dtype.itemsize
                  for x in jax.tree.leaves(abstract))
    kv_bytes = 2 * m["n_layers"] * m["n_kv_heads"] * m["head_dim"] * 2
    pool_bytes = geo["n_pages"] * geo["page_size"] * kv_bytes
    print(f"weights {w_bytes / GiB:.3f} GiB; KV {kv_bytes} B/token; pool "
          f"{geo['n_pages']} pages = {pool_bytes / GiB:.3f} GiB", flush=True)
    C = geo["prefill_chunk"]
    temp = c["mix"]["sampling"]["temperature"]
    for p in progs:
        args = [sds(a.shape, a.dtype)
                for a in programs._scratch_args(p, C, temp)]
        if p.kind == "decode":
            fn, call = adapter._fwd_paged_s, (adapter.weights, *args, pool,
                                              False)
        else:
            fn, call = adapter._fwd_prefill, (adapter.weights, *args, pool)
        t = time.perf_counter()
        lowered = fn.lower(*call)
        t_lower = time.perf_counter() - t
        t = time.perf_counter()
        mem = lowered.compile().memory_analysis()
        t_comp = time.perf_counter() - t
        tmp_b, args_b = mem.temp_size_in_bytes, mem.argument_size_in_bytes
        print(f"{p.name}: lower {t_lower:.1f}s compile {t_comp:.1f}s; "
              f"arguments {args_b / GiB:.3f} GiB, temporaries "
              f"{tmp_b / GiB:.3f} GiB, outputs "
              f"{mem.output_size_in_bytes / GiB:.3f} GiB (aliased "
              f"{mem.alias_size_in_bytes / GiB:.3f}); peak "
              f"{(args_b + tmp_b) / GiB:.3f} GiB", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
