"""Compile-only guards: the serving path's Pallas kernels at qwen3-14b
widths, compiled for a described TPU v5e chip (no chip attached).

The TPU compiler refuses things interpret mode accepts — unsupported
casts, block shapes off the (8, 128) tiling, too much VMEM — so these
tests catch a kernel that could never run on the chip.  The topology is
described inside a module fixture, never at import: only one process at a
time may load the TPU library, and every test worker imports this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import packing
from repro.kernels.paged_attention.ops import (
    paged_gqa_decode,
    paged_gqa_prefill,
    paged_gqa_verify,
)
from repro.kernels.quant_matmul.ops import quant_matmul

CFG = get_config("qwen3-14b")
KV, HD, H = CFG.n_kv_heads, CFG.head_dim, CFG.n_heads
PAGE, PAGES, PA = 16, 64, 16  # page size, pool pages, attended pages


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp
    # compiles are written to the persistent cache but cannot be read back
    # without a chip; keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("B", [8, 128], ids=["decode", "prefill"])
@pytest.mark.parametrize(
    "n,m", [(5120, 5120), (5120, 17408), (17408, 5120)],
    ids=["d-d", "d-ff", "ff-d"],
)
def test_quant_matmul_compiles(one_chip, n, m, B):
    rows = packing.packed_shape(m, n, 2)[0]
    text = _compiled_text(
        lambda x, p, s: quant_matmul(x, p, 2, n, s, 3, force_kernel=True),
        one_chip,
        ((B, n), jnp.bfloat16), ((rows, m), jnp.int32), ((), jnp.float32),
    )
    assert "tpu_custom_call" in text


def _pool_shapes(int8: bool, kv: int = KV):
    dt = jnp.int8 if int8 else jnp.bfloat16
    pages = ((CFG.n_layers, PAGES, kv, PAGE, HD), dt)
    scales = [((CFG.n_layers, PAGES, kv, PAGE), jnp.float32)] * 2
    return [pages, pages] + (scales if int8 else [])


def _pool_args(args, int8: bool):
    """Split a flat arg tuple ``(*pool, bt, ctx_len)`` into kwargs."""
    kp, vp, *rest = args
    kw = {}
    if int8:
        kw = {"k_scale": rest[0], "v_scale": rest[1]}
        rest = rest[2:]
    bt, cl = rest
    return kp, vp, bt, cl, kw


TABLES = [((8, PA), jnp.int32), ((8,), jnp.int32)]


# (int8, lanes, block-table width, KV heads): the chat cell dispatches 16
# lanes against 128- to 320-page tables; a two-way TP shard holds 2 KV
# heads
DECODE = [
    pytest.param(False, 8, PA, KV, id="bf16"),
    pytest.param(True, 8, PA, KV, id="int8"),
    pytest.param(False, 16, 128, KV, id="bf16-B16-Pa128"),
    pytest.param(False, 16, 320, KV, id="bf16-B16-Pa320"),
    pytest.param(False, 16, 256, 2, id="bf16-KV2"),
    pytest.param(True, 16, 256, 2, id="int8-KV2"),
]


@pytest.mark.parametrize("int8,B,Pa,kv", DECODE)
def test_paged_decode_compiles(one_chip, int8, B, Pa, kv):
    def step(q, kn, vn, *args):
        kp, vp, bt, cl, kw = _pool_args(args, int8)
        return paged_gqa_decode(q, kn, vn, kp, vp, bt, cl, layer=3,
                                force_kernel=True, **kw)

    h = kv * (H // KV)
    text = _compiled_text(
        step, one_chip,
        ((B, h, HD), jnp.bfloat16), ((B, kv, HD), jnp.bfloat16),
        ((B, kv, HD), jnp.bfloat16), *_pool_shapes(int8, kv),
        ((B, Pa), jnp.int32), ((B,), jnp.int32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("verify", [False, True], ids=["prefill", "verify"])
@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_paged_prefill_compiles(one_chip, int8, verify):
    C = 5 if verify else 64  # verify chunk is K+1 drafted tokens

    def step(q, kc, vc, ks, vs, *args):
        kp, vp, bt, cl, kw = _pool_args(args, int8)
        if verify:
            return paged_gqa_verify(q, kc, vc, kp, vp, bt, cl, layer=3,
                                    k_self=ks, v_self=vs, force_kernel=True,
                                    **kw)
        return paged_gqa_prefill(q, kc, vc, kp, vp, bt, cl, layer=3,
                                 force_kernel=True, **kw)

    chunk = ((8, C, KV, HD), jnp.bfloat16)
    text = _compiled_text(
        step, one_chip,
        ((8, C, H, HD), jnp.bfloat16), chunk, chunk, chunk, chunk,
        *_pool_shapes(int8), *TABLES,
    )
    assert "tpu_custom_call" in text
