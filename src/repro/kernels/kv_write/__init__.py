from repro.kernels.kv_write.kernel import (
    paged_kv_write,
    paged_kv_write_scales,
)
from repro.kernels.kv_write.ops import write_pages
from repro.kernels.kv_write.ref import write_pages_ref

__all__ = [
    "paged_kv_write",
    "paged_kv_write_scales",
    "write_pages",
    "write_pages_ref",
]
