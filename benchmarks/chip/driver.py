"""Drives the engine's ``submit``/``step``/``poll`` through one window.

Closed loop: ``clients`` callers each submit a request, and submit the
next as soon as the previous one is polled done.  They start together,
so the loop first serves a lead-in of ``lead_s`` seconds (set-up, not
measured) in which their requests spread out; the window opens after it.  Open loop: requests are
submitted when due on a fixed schedule, whether or not earlier ones have
finished; between arrivals with nothing to do the loop sleeps.  Each step
is timed on the host clock; the step ends in the engine's own device sync.

After the window no request is submitted that was not due in it, and the
engine is stepped until the requests due in the window have finished (or
``drain_s`` has passed), so every such request has its first token and
its output for the check.  Steps after the window are recorded but lie
outside it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

from benchmarks.chip import loadgen

DRAIN_S = 60.0


def _spans(on: bool):
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


def _fields(info) -> dict:
    """Every field of a ``StepInfo``, tuples as lists."""
    out = {}
    for f in dataclasses.fields(info):
        v = getattr(info, f.name)
        out[f.name] = list(v) if isinstance(v, tuple) else v
    return out


class Driver:
    def __init__(self, eng, traffic: dict, seed: int, vocab: int,
                 spans: bool = False):
        self.eng, self.traffic, self.seed, self.vocab = eng, traffic, seed, vocab
        self.span = _spans(spans)
        self.steps: list[dict] = []
        self.requests: dict[int, dict] = {}
        self.prompts: dict[int, tuple[int, ...]] = {}
        self.late_s = 0.0  # how far behind its schedule the open loop ran

    def submit(self, r: loadgen.Request, due: float) -> int:
        with self.span("bench.submit"):
            rid = self.eng.submit(list(r.prompt), r.max_new, quality=r.tier)
        self.requests[rid] = {"due": due, "prompt_len": len(r.prompt),
                              "max_new": r.max_new, "tier": r.tier,
                              "admitted_step": None, "finished_step": None,
                              "n_tokens": 0, "done": False}
        self.prompts[rid] = r.prompt
        return rid

    def step(self) -> list[int]:
        t0 = time.perf_counter()
        with self.span("bench.step"):
            info = self.eng.step()
        t1 = time.perf_counter()
        i = len(self.steps)
        self.steps.append({"t0": t0, "t1": t1, **_fields(info)})
        for rid in info.admitted:
            self.requests[rid]["admitted_step"] = i
        for rid in info.finished:
            self.requests[rid]["finished_step"] = i
        return list(info.finished)

    def run(self, seconds: float, on_open=None) -> tuple[float, float]:
        """Serve one window of ``seconds``; returns its (open, close).
        ``on_open`` is called once, just before the window opens."""
        on_open = on_open or (lambda: None)
        if self.traffic["loop"] == "closed":
            return self._closed(seconds, on_open)
        return self._open(seconds, on_open)

    def _closed(self, seconds: float, on_open) -> tuple[float, float]:
        todo = iter(loadgen.stream(self.traffic, self.seed, 1 << 16,
                                   self.vocab))
        for _ in range(self.traffic["clients"]):
            self.submit(next(todo), time.perf_counter())
        lead_end = time.perf_counter() + self.traffic.get("lead_s", 0.0)
        self._serve_closed(todo, lead_end, float("inf"))
        on_open()
        t_open = time.perf_counter()
        self._serve_closed(todo, t_open + seconds, t_open + seconds)
        t_close = self.steps[-1]["t1"]
        self._drain(t_close)
        return t_open, t_close

    def _serve_closed(self, todo, end: float, resubmit_until: float) -> None:
        """Step until ``end``; a client whose request is done submits its
        next one while the clock is before ``resubmit_until``."""
        while time.perf_counter() < end:
            for rid in self.step():
                with self.span("bench.poll"):
                    self.eng.poll(rid)
                if time.perf_counter() < resubmit_until:
                    self.submit(next(todo), time.perf_counter())

    def _open(self, seconds: float, on_open) -> tuple[float, float]:
        sched = loadgen.open_schedule(self.traffic, self.seed, seconds,
                                      self.vocab)
        on_open()
        t_open = time.perf_counter()
        end = t_open + seconds
        i = 0
        while True:
            now = time.perf_counter()
            if now >= end:
                break
            while i < len(sched) and t_open + sched[i].offset_s <= now:
                due = t_open + sched[i].offset_s
                self.late_s = max(self.late_s, now - due)
                self.submit(sched[i], due)
                i += 1
            if self.eng.has_work:
                for rid in self.step():
                    with self.span("bench.poll"):
                        self.eng.poll(rid)
            else:
                nxt = t_open + sched[i].offset_s if i < len(sched) else end
                with self.span("bench.wait"):
                    time.sleep(max(0.0, min(nxt, end) - now))
        t_close = self.steps[-1]["t1"] if self.steps else time.perf_counter()
        for r in sched[i:]:  # due in the window, reached after its close
            self.submit(r, t_open + r.offset_s)
        self._drain(t_close)
        return t_open, t_close

    def _drain(self, t_close: float) -> None:
        while self.eng.has_work and time.perf_counter() < t_close + DRAIN_S:
            self.step()

    def outputs(self) -> list[dict]:
        """Prompt, served tokens and tier of every request, after the run;
        fills ``n_tokens`` and ``done`` of the record."""
        from repro.serve import FinishReason

        out = []
        for rid, r in self.requests.items():
            st = self.eng.poll(rid)
            toks = list(st.tokens or [])
            r["n_tokens"] = len(toks) if st.tokens is not None else st.n_tokens
            r["done"] = (st.finish_reason is FinishReason.DONE
                         and len(toks) == r["max_new"])
            out.append({"rid": rid, "prompt": self.prompts[rid],
                        "tokens": toks, "tier": r["tier"], "done": r["done"]})
        return out
