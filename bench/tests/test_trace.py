"""The reduction from a trace to the per-layer metrics, on a small
hand-made trace whose answers are worked out by hand."""
import pytest

from harness import readers, work
from harness import trace as tr
from harness.peaks import peaks

MS = 1_000_000  # ns


def small_trace():
    # window 0..10 ms; ops (name, start, dur) in ns
    ops = [("fusion.1", 0 * MS, 1 * MS),
           ("quant_matmul.3", 1 * MS, 2 * MS),
           ("quant_matmul.4", 2 * MS, 2 * MS),     # overlaps the last
           ("paged_decode_attention.7", 5 * MS, 1 * MS),
           ("paged_prefill_attention.2", 7 * MS, 1 * MS),
           ("copy.9", 9 * MS, 3 * MS)]             # runs past the window
    modules = [("jit__forward_paged_sample", 0, 6 * MS),
               ("jit__forward_prefill_paged", 7 * MS, 1 * MS)]
    spans = [("step", 0, 10 * MS), ("decode", 4 * MS, 5 * MS),
             ("emit", 6 * MS, 7 * MS)]
    return tr.Trace(ops, modules, spans, (0, 10 * MS))


def test_busy_idle_and_gaps():
    t = small_trace()
    # busy: [0,4] + [5,6] + [7,8] + [9,10] = 7 ms of 10
    assert tr.busy_ns(t.ops, t.window) == 7 * MS
    assert tr.idle_gaps(t.ops, t.window) == [(4 * MS, MS), (6 * MS, MS),
                                             (8 * MS, MS)]
    named = dict(tr.name_gaps(tr.idle_gaps(t.ops, t.window), t.spans))
    # 4-5 ms: inside "decode"; 6-7: "emit" (innermost); 8-9: "step"
    assert named == {"decode": 0.001, "emit": 0.001, "step": 0.001}


def test_kernel_time_and_top_ops():
    t = small_trace()
    assert tr.kernel_ns(t.ops, t.window, ("quant_matmul",)) == 4 * MS
    top = dict(tr.top_ops(t.ops, t.window))
    assert top["quant_matmul"] == pytest.approx(0.004)
    assert top["copy"] == pytest.approx(0.001)  # clipped at the window


def test_idle_share_reader():
    from run import load_metric

    ctx = {"trace": small_trace()}
    assert load_metric("device_idle_share").read(ctx) == pytest.approx(30.0)


MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 16, "q_dim": 64, "kv_dim": 32, "d_ff": 128,
         "vocab": 100}
PEAK = {"bf16_flop_s": 1e12, "hbm_byte_s": 1e9}


def test_roofline_and_mfu_by_hand():
    from run import load_metric

    # one decode dispatch: 3 real lanes at contexts 10, 20, 33
    d = work.Dispatch("decode", [1, 1, 1], [10, 20, 33])
    # quant_matmul per layer: sum over linears of m*n = 64*64 (q) +
    # 2*32*64 (k, v) + 64*64 (o) + 2*128*64 (wi, wg) + 64*128 (wo)
    mn = 64 * 64 + 2 * 32 * 64 + 64 * 64 + 2 * 128 * 64 + 64 * 128
    mpn = (64 + 64) + 2 * (32 + 64) + (64 + 64) + 2 * (128 + 64) + (64 + 128)
    flops = 2 * 3 * mn * 2
    bytes_ = (mn * 2 / 8 + 2 * 3 * mpn) * 2
    assert work.quant_matmul(MODEL, d) == (flops, bytes_)
    # attention: 4 ctx hd per head per token; pages of 16: 1, 2, 3 pages
    att_fl = 4 * (10 + 20 + 33) * 16 * 4 * 2
    att_by = 2 * 2 * 2 * 16 * (1 + 2 + 3) * 16 * 2
    assert work.paged_attention(MODEL, d, 16) == (att_fl, att_by)
    t = small_trace()
    ctx = {"trace": t, "dispatches": [d], "model": MODEL, "peaks": PEAK,
           "page_size": 16, "outputs": 3, "notes": []}
    # quant_matmul: 4 ms of kernel time; bytes bound (bytes_/1e9 s)
    share = load_metric("quant_matmul_roofline").read(ctx)
    assert share == pytest.approx(
        100 * max(flops / 1e12, bytes_ / 1e9) / 0.004)
    assert "HBM bandwidth" in ctx["notes"][-1]
    share = load_metric("paged_attention_roofline").read(ctx)
    assert share == pytest.approx(
        100 * max(att_fl / 1e12, att_by / 1e9) / 0.002)
    mfu = load_metric("step_mfu").read(ctx)
    model_fl = flops + att_fl + 2 * 64 * 100 * 3
    assert mfu == pytest.approx(100 * model_fl / (0.010 * 1e12))


def test_program_time_readers():
    from run import load_metric

    d = work.Dispatch("prefill", [30, 10], [0, 64])
    ctx = {"trace": small_trace(), "dispatches": [d]}
    assert load_metric("decode_step_ms").read(ctx) == pytest.approx(6.0)
    # 1 ms of prefill program over 40 real tokens
    assert load_metric("prefill_ms_per_ktok").read(ctx) == pytest.approx(
        1.0 / 0.040)


def test_a_reader_with_nothing_to_read_returns_none():
    from run import load_metric

    t = tr.Trace([], [], [], (0, MS))
    ctx = {"trace": t, "dispatches": [], "model": MODEL, "peaks": PEAK,
           "page_size": 16, "outputs": 0, "notes": []}
    for name in ("quant_matmul_roofline", "paged_attention_roofline",
                 "decode_step_ms", "prefill_ms_per_ktok", "step_mfu"):
        assert load_metric(name).read(ctx) is None


def test_unknown_device_kind_raises():
    assert peaks("TPU v5 lite")["bf16_flop_s"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("TPU v9 imaginary")


@pytest.mark.parametrize("text, short", [
    ("%copy.70330 = bf16[40,2200,8,16,128]{4,3,2,1,0:T(8,128)(2,1)} "
     "copy(bf16[40,2200,8,16,128]{4,2,3,1,0:T(8,128)(2,1)} %fusion.564)",
     "copy.70330 bf16[40,2200,8,16,128]"),
    ("%paged_decode_attention.61 = (f32[16,8,5,128]{3,2,1,0:T(8,128)S(1)}, "
     "f32[16,8,5,1]{3,2,1,0}) custom-call(s32[16,320]{1,0} %copy-done.2458)",
     "paged_decode_attention.61 f32[16,8,5,128]"),
    ("quant_matmul.3", "quant_matmul.3"),
])
def test_op_names_from_hlo_text(text, short):
    """A TPU trace names each op by its whole HLO text; the kernels are
    found, and the top ops grouped, by the instruction's name."""
    assert tr.short_name(text) == short
    assert tr.base_name(short) == short.replace(
        short.split(" ")[0], short.split(" ")[0].rsplit(".", 1)[0], 1)
    ops = [(tr.short_name(text), 0, MS)]
    kernel = short.split(".")[0]
    assert tr.kernel_ns(ops, (0, MS), (kernel,)) == MS
