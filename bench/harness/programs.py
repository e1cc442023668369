"""A cell's compiled programs: which ones its traffic reaches, and warming
each of them once before the window.

The engine compiles one decode program per power-of-two page bucket of
the longest live context, and one prefill program per power-of-two lane
count and page bucket of the longest prior context.  Which of them a
cell reaches follows from its geometry and its request stream, so the
set is found by running the real engine's scheduler and page pool, driven
by the harness's own driver over the cell's own requests, with a
stand-in adapter that records each dispatch's shape and returns at once,
on a virtual clock.  A backlog is
scheduled the same way whatever the device's speed, so its set is exact;
an open-loop mix is replayed at several device speeds and the union
taken.
"""
from __future__ import annotations

import ctypes
import dataclasses
import time

import numpy as np


@dataclasses.dataclass(frozen=True, order=True)
class Program:
    kind: str  # "decode" | "prefill"
    lanes: int  # batch rows of the dispatch
    pages: int  # block-table width

    @property
    def name(self) -> str:
        return f"{self.kind}:{self.lanes}x{self.pages}"


def engine_config(cell: dict):
    """The knobs ``launch/serve.py --paged --paged-prefill`` takes."""
    import types

    return types.SimpleNamespace(
        slots=cell["n_slots"], page_size=cell["page_size"],
        pages=cell["n_pages"], token_budget=cell["token_budget"],
        prefill_chunk=cell["prefill_chunk"], paged=True,
        paged_prefill=True)


class _Recorder:
    """Stands in for ``CachedDecoder``: the engine plans, claims pages and
    emits exactly as it would, and each dispatch only records its shape."""

    paged = True

    def __init__(self, cfg):
        from repro.serve.faults import NO_FAULTS
        from repro.serve.telemetry import NULL_TRACER

        self.cfg, self.faults, self.tracer = cfg, NO_FAULTS, NULL_TRACER
        self.seen: list = []  # (tick, Program)
        self.tick = 0
        self.cost = 0.0  # modelled device seconds of the current tick

    def trace_tags(self):
        return {}

    def make_pool(self, **kw):
        from repro.serve.kv_cache import PagedKVPool

        return PagedKVPool(self.cfg, **kw)

    def prefill_paged(self, tokens, positions, bt, ctx_len, pages, offs,
                      pool):
        B, C = tokens.shape
        self.seen.append((self.tick, Program("prefill", B, bt.shape[1])))
        self.cost += self.model["prefill_token_s"] * B * C
        return np.zeros((B, C, 2), np.float32)

    def decode_paged_sample(self, tokens, positions, bt, ctx_len, pages,
                            offs, sampling, pool):
        B = tokens.shape[0]
        self.seen.append((self.tick, Program("decode", B, bt.shape[1])))
        self.cost += self.model["decode_tick_s"]
        return np.zeros((B, 1), np.int32), None


def _virtual_engine(adapter, cell: dict):
    """The real engine on a clock the caller advances (``eng.vt``)."""
    from repro.launch.serve import build_engine

    eng = build_engine(adapter, max_seq_len=cell["max_seq_len"],
                       args=engine_config(cell))
    eng.vt = 0.0
    eng.now = lambda: eng.vt
    return eng


def reachable(cell: dict, specs: list, until_s: float,
              speeds=(0.5, 1.0, 2.0)) -> list[Program]:
    """Every program the engine dispatches when ``harness.driver.Driver``
    drives ``specs`` (``traffic.Spec``): staging, then the stream for
    ``until_s`` modelled seconds, at each of the device speeds ``speeds``
    (multiples of the cell's ``tick_model``).  A backlog is scheduled
    alike at every speed, so it runs once, at the fastest: the most
    ticks."""
    from harness.driver import Driver
    from repro.configs.base import ArchConfig

    tiny = ArchConfig(name="sched", family="dense", n_layers=1, d_model=8,
                      n_heads=1, n_kv_heads=1, d_ff=8, vocab=2,
                      head_dim=8, dtype="float32")
    if all(s.arrival == 0.0 for s in specs):
        speeds = (max(speeds),)
    found: set = set()
    for speed in speeds:
        rec = _Recorder(tiny)
        rec.model = {k: v / speed for k, v in cell["tick_model"].items()}
        eng = _virtual_engine(rec, cell)
        real_tick = eng.tick

        def tick(rec=rec, eng=eng, real_tick=real_tick):
            rec.cost = 0.0
            res = real_tick()
            rec.tick += 1
            eng.vt += max(rec.cost, 1e-3)
            return res

        eng.tick = tick

        def wait(dt, eng=eng):
            eng.vt += dt

        drv = Driver(eng, specs, cell["in_flight"], wait=wait)
        drv.run_until(drv.start + until_s)
        found.update(p for _, p in rec.seen)
    return sorted(found)


def _scratch_args(p: Program, chunk: int, temperature: float) -> tuple:
    """Host arguments of one dispatch of ``p`` that touch only the
    scratch page (block tables, addresses and contexts all zero)."""
    z = lambda *s: np.zeros(s, np.int32)
    B = p.lanes
    if p.kind == "decode":
        return (z(B, 1), z(B, 1), z(B, p.pages), z(B), z(B), z(B),
                np.full(B, temperature, np.float32), np.ones(B, np.float32),
                z(B), z(B))
    return (z(B, chunk), z(B, chunk), z(B, p.pages), z(B), z(B, chunk),
            z(B, chunk))


def host_gib() -> float:
    """Resident memory of this process."""
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * 4096 / 2**30


def trim() -> None:
    """Hand freed heap back to the system: each full-width compile
    leaves about a GiB behind in the allocator otherwise, and a cell's
    twenty programs would fill the host."""
    try:
        malloc_trim = ctypes.CDLL("libc.so.6").malloc_trim
    except (OSError, AttributeError):  # not glibc
        return
    malloc_trim.argtypes, malloc_trim.restype = [ctypes.c_size_t], ctypes.c_int
    malloc_trim(0)


def warm(adapter, pool, programs: list, chunk: int, temperature: float,
         clock, log, threads: int = 4) -> list[dict]:
    """Compile every program, then run each once on the scratch page
    through the adapter entry the engine calls.  Lowering is Python and
    runs one program at a time; XLA compiles (or reads the compile cache)
    outside the GIL, ``threads`` programs at a time."""
    import concurrent.futures as cf

    import jax

    out = {p: {"program": p.name} for p in programs}
    lowered = {}
    for p in programs:
        t = time.perf_counter()
        args = adapter._place_tree(_scratch_args(p, chunk, temperature))
        if p.kind == "decode":
            lowered[p] = adapter._fwd_paged_s.lower(
                adapter.weights, *args, pool.arrays, False)
        else:
            lowered[p] = adapter._fwd_prefill.lower(
                adapter.weights, *args, pool.arrays)
        out[p]["lower_s"] = time.perf_counter() - t

    def compile_one(p):
        t = time.perf_counter()
        lowered[p].compile()
        lowered[p] = None
        trim()
        return p, time.perf_counter() - t

    before = clock.snapshot()
    t = time.perf_counter()
    with cf.ThreadPoolExecutor(threads) as ex:
        for p, secs in ex.map(compile_one, programs):
            out[p]["compile_s"] = secs
    wall = time.perf_counter() - t
    after = clock.snapshot()
    log(f"compiled {len(programs)} programs in {wall:.1f}s wall on "
        f"{threads} threads: {after['compiles'] - before['compiles']} "
        f"compiles, {after['cache_hits'] - before['cache_hits']} cache "
        f"reads; host memory {host_gib():.2f} GiB")
    for p in programs:
        before = clock.snapshot()
        t = time.perf_counter()
        args = _scratch_args(p, chunk, temperature)
        if p.kind == "decode":
            sel, _ = adapter.decode_paged_sample(*args[:6], args[6:], pool)
            np.asarray(sel[:, 0])  # the engine's token read
        else:
            logits = adapter.prefill_paged(*args, pool)
            np.asarray(logits[0, chunk - 1])  # the engine's boundary read
            del logits
        jax.block_until_ready(pool.arrays)
        after = clock.snapshot()
        rec = out[p]
        rec["first_run_s"] = time.perf_counter() - t
        rec["compiles_at_run"] = after["compiles"] - before["compiles"]
        log(f"warm {p.name}: lower {rec['lower_s']:.2f}s, compile "
            f"{rec['compile_s']:.2f}s, first run {rec['first_run_s']:.2f}s "
            f"({rec['compiles_at_run']} compiles at the run); host "
            f"{host_gib():.2f} GiB")
    return list(out.values())
