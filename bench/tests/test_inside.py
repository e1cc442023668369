"""The readers of the engine's own spans, the programs' named scopes and
the paged decode counters, on a small hand-made trace whose answers are
worked out by hand; and the scope of real compiled ops, on the CPU."""
import jax
import jax.numpy as jnp
import pytest

from harness import inside
from harness import trace as tr
from run import load_metric

MS = 1_000_000  # ns
DECODE = "jit__forward_paged_sample"
PREFILL = "jit__forward_prefill_paged"
SEVEN = ("tokens_per_tick", "decode_step_ms", "prefill_ms_per_ktok",
         "quant_matmul_roofline", "paged_attention_roofline", "step_mfu",
         "device_idle_share")


def hand_trace():
    # window 0..20 ms: a decode run 0-6, a prefill run 7-10, and an
    # eager first-token draw 12-12.5 inside a prefill:boundary span
    ops = [("fusion.1 f32[16,64]", 0, 1 * MS),        # incoherence/v
           ("quant_matmul.2 f32[16,64]", 1 * MS, 2 * MS),
           ("fusion.3 f32[16,64]", 3 * MS, MS // 2),  # incoherence/u_t
           ("sort.4 f32[16,100]", 4 * MS, 3 * MS // 2),  # sampler
           ("copy.5 f32[16,100]", 11 * MS // 2, MS // 2),
           ("fusion.1 f32[64,64]", 7 * MS, 1 * MS),   # prefill's v
           ("quant_matmul.2 f32[64,64]", 8 * MS, 2 * MS),
           ("sort.1 f32[1,1,100]", 12 * MS, MS // 2)]  # eager draw
    modules = [(f"{DECODE}(11)", 0, 6 * MS),
               (f"{PREFILL}(12)", 7 * MS, 3 * MS),
               ("jit_sort(13)", 12 * MS, MS // 2)]
    return tr.Trace(ops, modules, [("step", 0, 10 * MS)], (0, 20 * MS))


def scopes():
    top = "jit(_forward_paged_sample)/"
    decoy = {"fusion.9 f32[16,64]": top + "sampler/sort"}
    decode = {"fusion.1 f32[16,64]": top + "incoherence/v/dot_general",
              "quant_matmul.2 f32[16,64]": top + "jit(quant_matmul)/x",
              "fusion.3 f32[16,64]": top + "incoherence/u_t/dot_general",
              "sort.4 f32[16,100]": top + "sampler/vmap(vmap())/sort"}
    prefill = {"fusion.1 f32[64,64]":
               "jit(_forward_prefill_paged)/incoherence/v/dot_general"}
    return {DECODE: [decoy, decode], PREFILL: [prefill]}


def engine_spans():
    return [("step", 0, 10 * MS), ("decode", 1 * MS, 7 * MS),
            ("decode:wait", 4 * MS, 6 * MS),
            ("prefill:wait", 9 * MS, 19 * MS // 2),
            ("step", 10 * MS, 20 * MS), ("prefill", 10 * MS, 14 * MS),
            ("prefill:boundary", 11 * MS, 13 * MS),
            ("decode:wait", 15 * MS, 18 * MS)]


def ctx_of(**kw):
    ctx = {"trace": hand_trace(), "engine_spans": engine_spans(),
           "op_scopes": scopes(), "notes": [],
           "counters": {"steps": 2, "prefill_tokens": 40,
                        "decode_tokens": 26, "decode_pages_live": 30,
                        "decode_pages_attended": 64}}
    ctx.update(kw)
    return ctx


def test_host_ms_per_tick():
    ctx = ctx_of()
    # step 1: 10 ms less 2 + 0.5 waiting; step 2: 10 ms less 3
    assert load_metric("host_ms_per_tick").read(ctx) == pytest.approx(7.25)
    assert "decode:wait" in ctx["notes"][-1]  # the gaps, named


def test_incoherence_ms_per_decode():
    ctx = ctx_of()
    # decode run: fusion.1 (v, 1 ms) and fusion.3 (u_t, 0.5 ms); the
    # decoy executable of the same program holds none of the run's ops
    assert load_metric("incoherence_ms_per_decode").read(ctx) == \
        pytest.approx(1.5)
    note = ctx["notes"][-1]
    assert "u_t 33.3%" in note and "v 66.7%" in note
    assert "prefill 1 ms over 1 runs" in note


def test_sampler_ms_per_decode():
    ctx = ctx_of()
    assert load_metric("sampler_ms_per_decode").read(ctx) == \
        pytest.approx(1.5)
    assert "eager first-token draws 0.5 ms" in ctx["notes"][-1]


def test_decode_page_fill():
    assert load_metric("decode_page_fill").read(ctx_of()) == \
        pytest.approx(100 * 30 / 64)


def test_new_readers_read_nothing_on_a_program_without_them():
    """A program without the spans, scopes and page counters: each of
    these readers returns None and raises nothing."""
    ctx = ctx_of(engine_spans=[("step", 0, 10 * MS)],
                 op_scopes={DECODE: [{}], PREFILL: [{}]},
                 counters={"steps": 2, "prefill_tokens": 40,
                           "decode_tokens": 26})
    for name in ("host_ms_per_tick", "sampler_ms_per_decode",
                 "incoherence_ms_per_decode", "decode_page_fill"):
        assert load_metric(name).read(ctx) is None, name


def test_a_missing_trace_directory_reads_no_spans(monkeypatch, tmp_path):
    monkeypatch.setattr(inside, "TRACE_DIR", str(tmp_path / "none"))
    assert inside.spans({}) == []


@pytest.mark.parametrize("name", SEVEN)
def test_the_seven_readers_ignore_what_the_new_ones_read(name):
    from harness import work

    base = {"trace": hand_trace(), "notes": [], "model": {
        "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 16, "q_dim": 64, "kv_dim": 32, "d_ff": 128,
        "vocab": 100}, "peaks": {"bf16_flop_s": 1e12, "hbm_byte_s": 1e9},
        "page_size": 16, "outputs": 3, "counters": {
            "steps": 2, "prefill_tokens": 40, "decode_tokens": 26},
        "dispatches": [work.Dispatch("decode", [1, 1], [10, 20])]}
    full = {**ctx_of(), **{k: v for k, v in base.items()
                           if k != "counters"}}
    full["counters"] = {**base["counters"], "decode_pages_live": 30,
                        "decode_pages_attended": 64}
    metric = load_metric(name)
    assert metric.read(full) == metric.read(base)


HLO = """\
ENTRY %main.9 (p: f32[16,64]) -> f32[16,64] {
  %fusion.1 = f32[16,64]{1,0:T(8,128)} fusion(f32[16,64]{1,0} %p), \
kind=kLoop, calls=%fc.1, \
metadata={op_name="jit(f)/incoherence/v/dot_general" source_line=3}
  %copy.2 = f32[16,64]{1,0} copy(f32[16,64]{1,0} %fusion.1)
  ROOT %sort.3 = (f32[16,64]{1,0}, s32[16,64]{1,0}) sort(%copy.2), \
metadata={op_name="jit(f)/sampler/sort"}
}"""


def test_hlo_scopes_from_module_text():
    assert inside.hlo_scopes(HLO) == {
        "fusion.1 f32[16,64]": "jit(f)/incoherence/v/dot_general",
        "sort.3 f32[16,64]": "jit(f)/sampler/sort"}
    assert inside.sub_scope("jit(f)/incoherence/v/x", "incoherence") == "v"
    assert inside.in_scope("jit(f)/sampler/sort", "sampler")
    assert not inside.in_scope("jit(f)/samplers/sort", "sampler")


def test_op_scopes_found_in_the_compiled_programs_of_this_process():
    """On the CPU: the executable of a jitted function that ran is found,
    and its instructions carry the named scopes they were traced under."""

    @jax.jit
    def inside_scopes_probe(x):
        with jax.named_scope("incoherence"), jax.named_scope("v"):
            y = jnp.sin(x) @ x
        with jax.named_scope("sampler"):
            return jnp.sort(y, axis=-1)

    inside_scopes_probe(jnp.ones((8, 8))).block_until_ready()
    name = "jit_inside_scopes_probe"
    ctx = {"trace": tr.Trace([], [(f"{name}(1)", 0, MS)], [], (0, MS))}
    (table,) = inside.op_scopes(ctx)[name]
    paths = set(table.values())
    assert any(inside.in_scope(p, "sampler") for p in paths)
    assert any(inside.in_scope(p, "incoherence")
               and inside.sub_scope(p, "incoherence") == "v" for p in paths)
