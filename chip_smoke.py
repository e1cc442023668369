#!/usr/bin/env python3
"""Bring-up check: serve qwen3-14b at its published widths on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # tensor parallel + a replica fleet

One chip: writes a seeded random 2-bit artifact of qwen3-14b (40 layers,
d_model 5120, d_ff 17408, vocab 151936) to a temporary directory, serves
four ragged requests from it through ``repro.launch.serve.main`` on the
paged path, and checks the engine's prefill and decode logits against a
float32 reference forward (teacher-forced on the engine's own tokens, no
Pallas kernel, no page pool).  It also checks that the compiled serving
programs hold the Pallas kernels.

Four chips (depth cut to ``FOUR_CHIP_LAYERS``; widths stay published):
the parent process never touches JAX.  One child holds a (1, 4)
tensor-parallel engine and the one-chip engine on the same artifact to
the float32 reference, the TP engine within a small multiple of the
one-chip engine's error; a ``--fleet 4`` server then answers the same
requests, one replica per chip, and its streams must be token-identical
to the one-chip engine.

The last line of standard output is the JSON verdict, printed only when
every check passed.  Without a TPU the script exits nonzero.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

ARCH = "qwen3-14b"
SEED = 0
PROMPT_LENS = (37, 64, 129, 250)  # cross page (16) and chunk (64) edges
GEN = 24
FOUR_CHIP_LAYERS = 4
# engine knobs shared by every engine this script builds, so the one-chip
# run, the TP run and each fleet replica compile the same programs
ENGINE_FLAGS = [
    "--paged", "--paged-prefill", "--slots", "4", "--page-size", "16",
    "--prefill-chunk", "64", "--token-budget", "256",
    "--prompt-len", ",".join(map(str, PROMPT_LENS)), "--gen", str(GEN),
]
# Logit tolerances, relative to the RMS of the reference logits.  The
# engine computes in bfloat16 (unit roundoff 2^-9): every linear, norm and
# attention output is rounded, 40 layers deep, and the logits themselves
# are bf16.  Against a float32 reference that leaves a relative RMS error
# of a few percent (v5e, full width: 2.84% RMS, 15.7% max).  Faults
# planted in a CPU rehearsal (interpret-mode kernels; KV 8, head_dim 128,
# d_model 1024, 4 layers; sound: 0.63% / 3.4%, int8 pages 1.42% / 7.6%)
# read, as RMS / max: one block-table entry off by one page 48% / 388%;
# k and v scales of int8 pages swapped 33% / 246%; the 2-bit unpack
# shifted by one bit 141% / 665% -- each 6x or more past these bounds.
TOL_REL_RMS = 0.05
TOL_REL_MAX = 0.5
# The TP engine does the one-chip engine's bf16 arithmetic with the
# reductions split four ways, so against the same float32 reference its
# error should be of the same size; it may reach this multiple of the
# one-chip engine's reading, measured in the same run.  On four v5e
# chips the sound TP engine read 1.002x (RMS) and 1.007x (max); in a CPU
# rehearsal at reduced width it read 0.999x, while one shard's
# row-parallel partial sum 2% off read 3.9x and a bf16 psum 1.31x.
TP_VS_ONE_CHIP = 1.25
# seconds the whole four-chip path may take before it gives up
FOUR_CHIP_BUDGET_S = 480
T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[chip_smoke {time.perf_counter() - T0:6.1f}s] {msg}", flush=True)


def verdict(kind: str, count: int) -> str:
    return json.dumps({"ok": True, "device": {
        "platform": "tpu", "kind": kind, "count": count}})


# ---------------------------------------------------------------------------
# pieces that run in a process that holds the chip(s)
# ---------------------------------------------------------------------------


def require_tpu(count: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < count:
        raise SystemExit(
            f"chip_smoke needs {count} TPU chip(s); JAX sees "
            f"{len(devs)} {devs[0].platform} device(s)")
    return devs


def model_config(n_layers=None):
    import dataclasses

    from repro.configs import get_config

    cfg = get_config(ARCH)
    return cfg if n_layers is None else dataclasses.replace(
        cfg, n_layers=n_layers)


def write_artifact(directory: str, cfg) -> int:
    from repro.serve.artifacts import save_random_quantized

    t0 = time.perf_counter()
    path = save_random_quantized(directory, cfg, seed=SEED)
    size = sum(os.path.getsize(os.path.join(path, f))
               for f in os.listdir(path))
    log(f"artifact: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab} bytes={size} "
        f"write_s={time.perf_counter() - t0:.1f}")
    return size


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or fetching
    from the persistent cache), and persistent-cache hits."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring as mon

        self.seconds, self.hits = 0.0, 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in self.EVENTS:
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def kernel_calls(compiled_text: str) -> dict:
    """Pallas custom calls in a compiled TPU program, by kernel name."""
    import re

    out: dict = {}
    for name in re.findall(
            r"%([A-Za-z_]+)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"",
            compiled_text):
        out[name] = out.get(name, 0) + 1
    return out


def check_kernels(adapter, pool) -> None:
    """Compile (from the persistent cache when warm) the decode and
    prefill programs at the shapes the run used, and require the Pallas
    kernels in both."""
    from repro.serve.kv_cache import page_bucket, pages_needed

    B, cap = pool.n_slots, pool.max_pages_per_seq
    z = lambda *s: np.zeros(s, np.int32)
    pa = page_bucket(pages_needed(max(PROMPT_LENS) + GEN - 2,
                                  pool.page_size), cap)
    dec = adapter._fwd_paged_s.lower(
        adapter.weights, z(B, 1), z(B, 1), z(B, pa), z(B), z(B), z(B),
        np.zeros(B, np.float32), np.ones(B, np.float32), z(B), z(B),
        pool.arrays, True).compile().as_text()
    Bp, C = page_bucket(len(PROMPT_LENS), 1 << 16), 64
    pre = adapter._fwd_prefill.lower(
        adapter.weights, z(Bp, C), z(Bp, C), z(Bp, 1), z(Bp), z(Bp, C),
        z(Bp, C), pool.arrays).compile().as_text()
    found = {"decode": kernel_calls(dec), "prefill": kernel_calls(pre)}
    log(f"kernels in compiled programs: {found}")
    for prog, attn in (("decode", "paged_decode_attention"),
                       ("prefill", "paged_prefill_attention")):
        for k in ("quant_matmul", attn):
            if not found[prog].get(k):
                raise SystemExit(f"{prog} program has no {k} kernel")


def reference_logits(adapter, reqs) -> list:
    """float32 logits of the teacher-forced sequence prompt + emitted
    tokens, at every position the engine emitted from.  Every linear
    dequantizes its codes in jnp (``QuantizedLinear._matmul`` with
    ``use_kernel=False``); attention is dense over the whole sequence."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.core.quantizer import QuantizedLinear
    from repro.launch.quantize import _quantized_block_forward
    from repro.models import layers as L

    cfg = adapter.cfg

    def ref_linear(q: QuantizedLinear) -> QuantizedLinear:
        # transform seeds are metadata only; equal ones give every layer
        # the same pytree structure, so one compile serves all 40
        st = dataclasses.replace(
            q.state, U=dataclasses.replace(q.state.U, seed=0),
            V=dataclasses.replace(q.state.V, seed=0))
        return dataclasses.replace(q, state=st, use_kernel=False)

    block = jax.jit(lambda blk, x, pos: _quantized_block_forward(
        blk, x, cfg, pos))
    head = jax.jit(lambda emb, fn, x: L.lm_logits(
        emb, L.norm_apply(fn, x, cfg)))
    seqs = [np.concatenate([r.prompt, r.out_tokens[:-1]]).astype(np.int32)
            for r in reqs]
    S = max(len(s) for s in seqs)
    toks = np.zeros((len(seqs), S), np.int32)
    for i, s in enumerate(seqs):
        toks[i, : len(s)] = s  # right padding: causal, never attended
    with jax.default_matmul_precision("highest"):
        x = L.embed(adapter.embed, toks).astype(jnp.float32)
        pos = jnp.arange(S, dtype=jnp.int32)
        for blk in adapter.blocks:
            blk = {k: ref_linear(v) if isinstance(v, QuantizedLinear) else v
                   for k, v in blk.items()}
            x = block(blk, x, pos)
        rows = np.stack([np.arange(GEN) + len(r.prompt) - 1 for r in reqs])
        x = jnp.take_along_axis(x, rows[:, :, None], axis=1)
        logits = head(adapter.embed, adapter.final_norm, x)
    return [np.asarray(l, np.float32) for l in logits]


def compare_logits(got: list, want: list, label: str,
                   tol_rms: float = TOL_REL_RMS,
                   tol_max: float = TOL_REL_MAX) -> dict:
    """Relative errors of ``got`` vs ``want`` (lists of (T, V) arrays) and
    greedy agreement; raises when out of tolerance or not finite."""
    g = np.concatenate(got).astype(np.float64)
    w = np.concatenate(want).astype(np.float64)
    if not np.all(np.isfinite(g)):
        raise SystemExit(f"{label}: engine logits hold NaN/Inf")
    scale = float(np.sqrt(np.mean(w * w)))
    rel_rms = float(np.sqrt(np.mean((g - w) ** 2))) / scale
    rel_max = float(np.max(np.abs(g - w))) / scale
    agree = float(np.mean(np.argmax(g, -1) == np.argmax(w, -1)))
    log(f"{label}: rel_rms_err={rel_rms:.6g} (tol {tol_rms:.6g}) "
        f"rel_max_err={rel_max:.6g} (tol {tol_max:.6g}) "
        f"ref_logit_rms={scale:.6g} greedy_agreement={agree:.6g} "
        f"over {g.shape[0]} positions, finite=True")
    if rel_rms > tol_rms or rel_max > tol_max:
        raise SystemExit(f"{label}: logits out of tolerance")
    return {"rel_rms": rel_rms, "rel_max": rel_max, "agree": agree}


def engine_logits(reqs) -> list:
    return [np.stack(r.step_logits) for r in reqs]


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def one_chip() -> int:
    devs = require_tpu(1)
    from repro.launch import serve
    from repro.runtime.compile_cache import use_compile_cache

    kind = devs[0].device_kind
    log(f"device_kind={kind} devices={len(devs)} "
        f"compile_cache={use_compile_cache()}")
    clock = CompileClock()
    tmp = tempfile.mkdtemp(prefix="quip-smoke-")
    try:
        write_artifact(tmp, model_config())
        run: dict = {}

        def on_done(engine, submitted):
            run["t"] = time.perf_counter()
            run["engine"], run["reqs"] = engine, [r for _, r in submitted]

        t0 = time.perf_counter()
        rc = serve.main(["--load-quantized", tmp, "--arrival-gap", "0",
                         "--requests", str(len(PROMPT_LENS)), *ENGINE_FLAGS],
                        on_done=on_done)
        if rc != 0 or "engine" not in run:
            raise SystemExit(f"serve.main exited {rc}")
        eng = run["engine"]
        load_s = eng._t0 - t0
        log(f"serve: rc={rc} load_s={load_s:.1f} "
            f"serve_s={run['t'] - eng._t0:.1f} compile_s={clock.seconds:.1f} "
            f"cache_hits={clock.hits}")
        stats = devs[0].memory_stats() or {}
        log(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
        reqs = run["reqs"]
        if any(len(r.out_tokens) != GEN for r in reqs):
            raise SystemExit("a request did not emit all its tokens")
        check_kernels(eng.adapter, eng.pool)
        t1 = time.perf_counter()
        want = reference_logits(eng.adapter, reqs)
        log(f"reference: float32 forward over {len(reqs)} sequences in "
            f"{time.perf_counter() - t1:.1f}s")
        compare_logits(engine_logits(reqs), want,
                       "engine vs float32 reference")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(verdict(kind, 1))
    return 0


# ---------------------------------------------------------------------------
# four chips: the parent stays off JAX, each step is a child process
# ---------------------------------------------------------------------------


def tp_child(directory: str) -> int:
    """Child: the one-chip engine and the (1, 4) TP engine serve the same
    requests one at a time (the shapes a lone fleet replica compiles).
    Each is held to the float32 reference, teacher-forced on its own
    tokens; the TP engine may stray at most ``TP_VS_ONE_CHIP`` times as
    far as the one-chip engine does.  Writes the one-chip token streams
    for the fleet step."""
    import types

    import jax

    devs = require_tpu(4)
    from repro.data import make_calibration
    from repro.launch import serve
    from repro.runtime.compile_cache import use_compile_cache
    from repro.serve import CachedDecoder, DistributedCachedDecoder
    from repro.serve import make_serving_mesh
    from repro.serve.artifacts import load_quantized

    use_compile_cache()
    cfg = model_config(FOUR_CHIP_LAYERS)
    write_artifact(directory, cfg)
    calib = make_calibration(cfg.vocab, n_segments=len(PROMPT_LENS),
                             seg_len=max(PROMPT_LENS), seed=SEED + 3).tokens
    prompts = [calib[i, :n] for i, n in enumerate(PROMPT_LENS)]
    knobs = types.SimpleNamespace(
        slots=4, page_size=16, pages=None, token_budget=256,
        prefill_chunk=64, paged=True, paged_prefill=True)

    def serve_one_by_one(adapter):
        out = []
        for p in prompts:
            eng = serve.build_engine(
                adapter, max_seq_len=max(PROMPT_LENS) + GEN, args=knobs,
                record_logits=True)
            req = eng.submit(p, max_new=GEN)
            eng.run()
            out.append(req)
        return out, eng.pool

    with jax.default_device(devs[0]):
        qm, _ = load_quantized(directory)
        one_adapter = CachedDecoder.from_quantized(qm)
        one, _ = serve_one_by_one(one_adapter)
    log(f"one-chip engine served {len(one)} requests")
    mesh = make_serving_mesh(1, 4)
    tp_adapter, _ = DistributedCachedDecoder.load(directory, mesh=mesh)
    tp, pool = serve_one_by_one(tp_adapter)
    packed = tp_adapter.blocks[0]["mlp.wi"].packed
    on = lambda a: sorted(str(s.device) for s in a.addressable_shards)
    log(f"tp placement: mesh={dict(mesh.shape)} mlp.wi packed "
        f"{packed.shape} -> shards {packed.addressable_shards[0].data.shape} "
        f"on {on(packed)}; kv pool {pool.k.shape} -> shards "
        f"{pool.k.addressable_shards[0].data.shape} on {on(pool.k)}")
    if len({str(s.device) for s in packed.addressable_shards}) != 4 or \
            len({str(s.device) for s in pool.k.addressable_shards}) != 4:
        raise SystemExit("TP arrays did not land on 4 devices")
    if packed.addressable_shards[0].data.shape == packed.shape:
        raise SystemExit("TP weights are replicated, not sharded")
    with jax.default_device(devs[0]):
        base = compare_logits(engine_logits(one),
                              reference_logits(one_adapter, one),
                              "one-chip engine vs float32 reference")
        tp_ref = reference_logits(one_adapter, tp)
    compare_logits(engine_logits(tp), tp_ref,
                   "tp(1,4) engine vs float32 reference",
                   tol_rms=TP_VS_ONE_CHIP * base["rel_rms"],
                   tol_max=TP_VS_ONE_CHIP * base["rel_max"])
    with open(os.path.join(directory, "one_chip.json"), "w") as f:
        json.dump({"prompts": [p.tolist() for p in prompts],
                   "tokens": [r.out_tokens for r in one],
                   "kind": devs[0].device_kind}, f)
    return 0


def _http(port: int, method: str, path: str, body=None, timeout=5.0):
    """One JSON request to a local server -> (status, decoded body)."""
    import http.client

    c = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        c.request(method, path, None if body is None else json.dumps(body),
                  {"Content-Type": "application/json"})
        r = c.getresponse()
        return r.status, json.loads(r.read())
    finally:
        c.close()


def _generate_all(port: int, prompts: list, deadline: float) -> list:
    """Token streams of ``prompts`` served one at a time by ``port``."""
    out = []
    for prompt in prompts:
        status, body = _http(port, "POST", "/v1/generate", {
            "prompt": prompt, "max_new": GEN, "stream": False},
            timeout=max(1.0, deadline - time.time()))
        if status != 200:
            raise SystemExit(f"port {port} answered {status}: {body}")
        out.append(body["tokens"])
    return out


def fleet_step(directory: str, deadline: float) -> None:
    """Parent: serve ``--fleet 4`` from the artifact.  Every replica
    first answers the requests itself (which also compiles its programs,
    so the router's short upstream timeouts never see a compile); then
    the router answers them.  Every stream must equal the one-chip
    engine's.  Gives up at ``deadline`` (``time.time()``)."""
    from concurrent.futures import ThreadPoolExecutor

    from repro.serve.fleet import free_port
    from repro.serve.fleet.supervisor import local_tpu_chips

    with open(os.path.join(directory, "one_chip.json")) as f:
        ref = json.load(f)
    chips = local_tpu_chips()
    if chips < 4:
        raise SystemExit(f"the fleet step needs 4 TPU chips on this host; "
                         f"its device nodes show {chips}")
    port = free_port()
    env = {**os.environ, "PYTHONPATH": os.path.join(HERE, "src")}
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.serve", "--fleet", "4",
         "--router-port", str(port), "--load-quantized", directory,
         "--tick-stall-s", "600", *ENGINE_FLAGS], env=env,
        start_new_session=True)
    try:
        while True:
            if proc.poll() is not None:
                raise SystemExit(f"fleet exited {proc.returncode} at start")
            try:
                fz = _http(port, "GET", "/fleetz")[1]
                if all(r["state"] == "healthy" for r in fz["replicas"]):
                    break
            except (OSError, ValueError):
                pass
            if time.time() > deadline:
                raise SystemExit("fleet replicas not healthy in time")
            time.sleep(1.0)
        log("fleet: all 4 replicas healthy")
        t0 = time.perf_counter()
        with ThreadPoolExecutor(4) as ex:
            direct = list(ex.map(lambda r: _generate_all(
                r["port"], ref["prompts"], deadline), fz["replicas"]))
        log(f"fleet: each replica answered {len(ref['prompts'])} requests "
            f"directly in {time.perf_counter() - t0:.1f}s (cold)")
        routed = _generate_all(port, ref["prompts"], deadline)
        fz = _http(port, "GET", "/fleetz")[1]
    finally:
        proc.terminate()
        try:
            rc = proc.wait(timeout=60)
        except subprocess.TimeoutExpired:  # the router and its replicas
            os.killpg(proc.pid, signal.SIGKILL)
            rc = proc.wait()
    same_direct = [sum(a == b for a, b in zip(d, ref["tokens"]))
                   for d in direct]
    same = sum(a == b for a, b in zip(routed, ref["tokens"]))
    log(f"fleet: rc={rc} per-replica token-identical streams="
        f"{same_direct} routed token-identical streams={same}/"
        f"{len(routed)} served={[r['served'] for r in fz['replicas']]} "
        f"failovers={fz['journal']['failovers']}")
    n = len(ref["tokens"])
    if rc != 0 or same != n or any(s != n for s in same_direct):
        raise SystemExit("fleet streams differ from the one-chip engine")


def four_chips() -> int:
    deadline = time.time() + FOUR_CHIP_BUDGET_S
    tmp = tempfile.mkdtemp(prefix="quip-smoke4-")
    try:
        rc = subprocess.run([sys.executable, os.path.abspath(__file__),
                             "--tp-child", tmp],
                            timeout=FOUR_CHIP_BUDGET_S).returncode
        if rc != 0:
            raise SystemExit(f"tp step failed ({rc})")
        fleet_step(tmp, deadline)
        with open(os.path.join(tmp, "one_chip.json")) as f:
            kind = json.load(f)["kind"]
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(verdict(kind, 4))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--tp-child", metavar="DIR", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.tp_child:
        return tp_child(args.tp_child)
    return four_chips() if args.chips == 4 else one_chip()


if __name__ == "__main__":
    sys.exit(main())
