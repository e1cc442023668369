"""The open-loop driver: submit the cell's requests at their scheduled
arrivals, tick the engine, and stamp every token when the tick that made
it returns it to the host.

All times are on the engine's clock (``Engine.now()``, the host's
``perf_counter`` from an epoch), read by the harness itself.  The same
driver replays a cell on a virtual clock to find its programs
(``programs.reachable``): there ``wait`` advances that clock.
"""
from __future__ import annotations

import time

import numpy as np


class Driver:
    def __init__(self, engine, specs, staged: int, wait=time.sleep):
        """The first ``staged`` of ``specs`` (``traffic.Spec``) are already
        in flight: each is submitted and prefilled in turn, the next once
        the one before it decodes, so the window opens on lanes at
        staggered progress and staging prefills one lane at a time.  The
        rest arrive ``spec.arrival`` after staging ends (``self.start``).
        ``wait(seconds)`` passes idle time."""
        self.engine, self.wait = engine, wait
        self.reqs, self.specs, self.times = [], {}, {}
        self.ticks: list = []  # (start, end) of each tick
        for s in specs[:staged]:
            r = self._submit(s, engine.now())
            while r.state.name in ("QUEUED", "PREFILL"):
                self.tick()
        self.start = engine.now()
        for s in specs[staged:]:
            self._submit(s, self.start + s.arrival)

    def _submit(self, s, arrival: float):
        from repro.serve.scheduler import SamplingParams

        r = self.engine.submit(
            s.prompt, s.max_new, arrival=arrival,
            sampling=SamplingParams(temperature=s.temperature,
                                    top_p=s.top_p, seed=s.sample_seed))
        self.reqs.append(r)
        self.specs[r.rid] = s
        self.times[r.rid] = []
        return r

    def tick(self) -> None:
        eng = self.engine
        now = eng.now()
        res = eng.tick()
        t = eng.now()
        for req, _tok in res.emitted:
            self.times[req.rid].append(t)
        self.ticks.append((now, t))

    def run_until(self, t_end: float) -> None:
        """Tick until ``t_end``; a tick that starts before it runs to its
        end.  Waits while nothing has arrived."""
        eng = self.engine
        while True:
            now = eng.now()
            if now >= t_end:
                return
            if not eng.running and not eng.scheduler.queue:
                nxt = eng.next_arrival()
                if nxt is None:
                    return
                if nxt > now:
                    self.wait(min(nxt, t_end) - now)
                    continue
            self.tick()

    def window(self, t0: float, t1: float) -> dict:
        """End-to-end readings of the window ``(t0, t1]``."""
        out_tokens = 0
        gaps, ttft = [], []
        attempted = failed = 0
        for r in self.reqs:
            ts = np.asarray(self.times[r.rid])
            inside = (ts > t0) & (ts <= t1)
            out_tokens += int(inside.sum())
            if len(ts) > 1:
                later = ts[1:]
                sel = (later > t0) & (later <= t1)
                gaps.extend((later - ts[:-1])[sel])
            # attempted: the window served it or the client sent it then
            # (a backlog's requests not yet admitted wait their turn)
            done_before = len(ts) and r.state.terminal and ts[-1] <= t0
            admitted = r.t_admitted is not None and r.t_admitted <= t1
            sent = t0 <= r.arrival < t1
            if (admitted or sent) and not done_before:
                attempted += 1
                failed += r.state.name in ("FAILED", "CANCELLED")
            if t0 <= r.arrival < t1:
                first = ts[0] if len(ts) else np.inf
                ttft.append(min(first, t1) - r.arrival)
        return {"output_tokens": out_tokens, "gaps": np.asarray(gaps),
                "ttft": np.asarray(ttft), "attempted": attempted,
                "failed": failed}


def pctl(xs, q: float):
    """numpy's linear-interpolated percentile; None for no samples."""
    xs = np.asarray(xs, np.float64)
    return float(np.percentile(xs, q)) if xs.size else None
