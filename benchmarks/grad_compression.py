import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=16")
# ^ standalone module (run via `python -m benchmarks.grad_compression`):
# needs a 16-device data axis to materialize the gradient all-reduce.

"""Distributed-optimization trick, measured: int8 error-feedback gradient
compression over the data axis.

Lowers two shard_map gradient-sync steps on a 16-way data mesh and counts
collective link-bytes in the compiled HLO:

  fp32 baseline:  g_mean = psum(g) / 16         (ring: 2 x 4 B/elem x 15/16)
  int8-EF:        q, s, e = ef_compress(g)
                  phase 1: all_to_all the int8 chunks (reduce-scatter with
                           int8 on the wire), accumulate locally in f32;
                  phase 2: requantize the reduced chunk to int8 and
                           all-gather it (int8 on the wire again).
                  => 2 x 1 B/elem x 15/16 vs 2 x 4 -> ~4x fewer link bytes

The error-feedback buffer keeps the quantization residual local, so the
compression is unbiased over steps (tests/test_substrate.py proves the
accumulation property)."""
import json

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.optim.compression import ef_int8_compress, init_ef_state
from repro.runtime.compile_cache import use_compile_cache
from repro.runtime.hlo_analysis import analyze_hlo


def main(argv=None):
    use_compile_cache()
    n_dev = len(jax.devices())
    mesh = Mesh(jax.devices(), ("data",))
    nelem = 1 << 20  # 1M-element gradient leaf (4 MB fp32)

    gspec = jax.ShapeDtypeStruct((n_dev, nelem), jnp.float32)
    espec = jax.ShapeDtypeStruct((n_dev, nelem), jnp.float32)

    @jax.jit
    def sync_fp32(g):
        def f(g):
            return jax.lax.psum(g, "data") / n_dev

        return jax.shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P())(g)

    @jax.jit
    def sync_int8(g, e):
        def f(g, e):
            q, s, err = ef_int8_compress({"g": g[0]}, {"g": e[0]})
            # phase 1: int8 reduce-scatter (all_to_all keeps int8 on the
            # wire; the accumulate happens locally in f32 — a direct int8
            # psum would overflow)
            chunks = q["g"].reshape(n_dev, -1)  # (n_dev, nelem/n_dev) int8
            mine = jax.lax.all_to_all(
                chunks, "data", split_axis=0, concat_axis=0, tiled=False
            )  # (n_dev, chunk) int8: everyone's contribution to my chunk
            sg = jax.lax.all_gather(s["g"], "data")  # (n_dev,) f32 scales
            red = jnp.einsum(
                "dn,d->n", mine.astype(jnp.float32), sg
            ) / n_dev  # (chunk,) f32 reduced mean
            # phase 2: requantize + int8 all-gather
            s2 = jnp.max(jnp.abs(red)) / 127.0 + 1e-12
            q2 = jnp.clip(jnp.round(red / s2), -127, 127).astype(jnp.int8)
            qg = jax.lax.all_gather(q2, "data")  # (n_dev, chunk) int8
            s2g = jax.lax.all_gather(s2, "data")  # (n_dev,) f32
            mean = (qg.astype(jnp.float32) * s2g[:, None]).reshape(-1)
            return mean, err["g"][None]

        return jax.shard_map(
            f, mesh=mesh, in_specs=(P("data"), P("data")),
            out_specs=(P(), P("data")), check_vma=False,
        )(g, e)

    results = {}
    for name, fn, args in (
        ("fp32", sync_fp32, (gspec,)),
        ("int8_ef", sync_int8, (gspec, espec)),
    ):
        hlo = fn.lower(*args).compile().as_text()
        st = analyze_hlo(hlo, n_dev)
        results[name] = st.collectives.total_bytes
        print(f"grad_compression/{name},0.0,"
              f"collective_bytes={st.collectives.total_bytes/1e6:.2f}MB "
              f"{st.collectives.summary()['by_kind']}")
    ratio = results["fp32"] / max(results["int8_ef"], 1)
    print(f"grad_compression/ratio,0.0,fp32/int8 = {ratio:.2f}x fewer "
          f"link bytes (theory ~4x: int8 wire both phases, EF keeps it "
          f"unbiased over steps)")
    with open("experiments/grad_compression.json", "w") as f:
        json.dump({**results, "ratio": ratio}, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
