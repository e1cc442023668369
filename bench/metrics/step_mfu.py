"""Model step: FLOPs the model needs for the real tokens processed in
the traced window (``harness.work.model_flops``), over the window's
seconds times the chip's bf16 peak, in percent."""
from harness.readers import step_mfu


def read(ctx):
    return step_mfu(ctx)
