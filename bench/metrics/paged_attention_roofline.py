"""Kernels: the paged-attention Pallas kernels' (decode and prefill
together) share of their roofline.  Work is counted from the model's
shapes and each dispatch's real lanes and contexts
(``harness.work.paged_attention``)."""
from harness import work
from harness.readers import roofline

KERNELS = ("paged_decode_attention", "paged_prefill_attention")


def read(ctx):
    page = ctx["page_size"]
    return roofline(ctx, KERNELS,
                    lambda m, d: work.paged_attention(m, d, page))
