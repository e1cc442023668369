"""Operations and bytes the model needs, counted from its shapes and the
real lanes and contexts of each traced dispatch, never from a kernel's
launch shapes: padding, tiling or a fused replacement cannot change them.

A dispatch record (``Dispatch``) holds, per real lane, the tokens it
carried and the context before them.
"""
from __future__ import annotations

import dataclasses

from harness.weights import BITS, linear_shapes


@dataclasses.dataclass
class Dispatch:
    kind: str  # "decode" | "prefill"
    tokens: list  # real tokens per real lane
    ctx: list  # context tokens before them, per real lane


def quant_matmul(m: dict, d: Dispatch) -> tuple[float, float]:
    """FLOPs 2 rows m n and bytes m n bits/8 + bf16 x and y, per linear,
    over every layer; rows are the dispatch's real tokens."""
    rows = sum(d.tokens)
    fl = by = 0.0
    for mm, nn in linear_shapes(m).values():
        fl += 2.0 * rows * mm * nn
        by += mm * nn * BITS / 8 + 2.0 * rows * (mm + nn)
    return fl * m["n_layers"], by * m["n_layers"]


def paged_attention(m: dict, d: Dispatch, page_size: int):
    """FLOPs 4 ctx hd per query head and real token (the chunk's causal
    block included for prefill); bytes: the real context's K/V pages
    (and a prefill chunk's own K/V), bf16, over every layer."""
    hd, H, KV = m["head_dim"], m["n_heads"], m["n_kv_heads"]
    fl = by = 0.0
    for n, c in zip(d.tokens, d.ctx):
        attended = n * c + (n * (n + 1) // 2 if d.kind == "prefill" else 0)
        fl += 4.0 * attended * hd * H
        pages = -(-c // page_size)
        own = n if d.kind == "prefill" else 0
        by += 2 * 2 * KV * hd * (pages * page_size + own)
    return fl * m["n_layers"], by * m["n_layers"]


def model_flops(m: dict, dispatches: list, outputs: int) -> float:
    """FLOPs of the model for the real tokens of ``dispatches``: every
    linear and attention over the context, plus the head once for each
    of the ``outputs`` tokens served."""
    fl = sum(quant_matmul(m, d)[0] + paged_attention(m, d, 1)[0]
             for d in dispatches)
    return fl + 2.0 * m["d_model"] * m["vocab"] * outputs
