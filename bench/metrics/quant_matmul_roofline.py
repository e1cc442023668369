"""Kernels: the 2-bit ``quant_matmul`` Pallas kernel's share of its
roofline.  Work is counted from the model's shapes and each dispatch's
real tokens (``harness.work.quant_matmul``); time is the summed device
time of the kernel's ops."""
from harness import work
from harness.readers import roofline

KERNELS = ("quant_matmul",)


def read(ctx):
    return roofline(ctx, KERNELS, work.quant_matmul)
