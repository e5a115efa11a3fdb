"""Serving cells: ``ServeEngine`` over the configuration's backend, driven
by an open or a closed loop of requests from ``traffic.requests``.

Set-up makes the weights, builds the engine and its cache, and runs one
short request through every program the window uses (slot reset, block
prefill, decode step, sampling); a closed loop then admits its first
requests, an open loop serves the part of its schedule before the window.
The window runs engine ticks until ``seconds`` have passed and the last
tick has ended. Every token is stamped with the end of the tick that
committed it.
"""
from __future__ import annotations

import gc
import math
import time

import numpy as np

from bench import plugins, reference, traffic, weights
from bench.spec import model_config, serve_config
from bench.tracing import WINDOW

HOST_SPANS = ("admit", "step", "wait", "client")


def p95(values) -> float:
    return float(np.percentile(np.asarray(values, np.float64), 95))


def build_backend(cfg, scfg, params, conf, devices):
    """The backend the configuration names (``serve.backend``), built by
    ``backends/<backend>.py`` over the run's devices."""
    return plugins.backend(conf).build(cfg, scfg, params, conf, devices)


class Window:
    """Host-side record of one serving window."""

    def __init__(self, engine, mix, reqs):
        from jax.profiler import TraceAnnotation
        self.ann = TraceAnnotation
        self.engine = engine
        self.mix = mix
        self.reqs = reqs
        self.next = 0                       # next request of the pool
        self.live: dict = {}                # id(handle) -> Req
        self.sent: list = []
        self.ticks: list = []               # (rows, sum of positions)
        self.prefills: list = []            # block-prefilled tokens
        self.lateness: list = []
        self.origin = time.perf_counter()
        self.w0 = self.w1 = 0.0

    def submit(self, now: float) -> None:
        r = self.reqs[self.next]
        self.next += 1
        r.handle = self.engine.sched.submit(r.prompt, r.max_new)
        if self.mix["kind"] == "closed_loop":
            r.due = now
        self.lateness.append(now - r.due)
        self.live[id(r.handle)] = r
        self.sent.append(r)

    def admit(self, now: float) -> None:
        backend = self.engine.backend
        before = [r for r in self.live.values() if math.isnan(r.admitted)]
        self.engine._admit()
        for r in before:
            if r.handle.status != "queued":
                r.admitted = now
                n = backend.prefill_len(len(r.prompt))
                r.consumed = n
                if n > 0:
                    self.prefills.append(n)

    def step(self) -> None:
        sched = self.engine.sched
        rows = [self.live[id(h)] for h in sched.slot_req if h is not None]
        self.ticks.append((len(rows), sum(r.consumed for r in rows)))
        self.engine.step()
        now = time.perf_counter() - self.origin
        for r in rows:
            r.consumed += 1
            new = len(r.handle.out_tokens) - len(r.token_times)
            r.token_times += [now] * new
            if r.handle.status != "running":
                del self.live[id(r.handle)]
                r.finished = now
                if self.mix["kind"] == "closed_loop" and \
                        self.next < len(self.reqs):
                    self.submit(now)

    def _loop(self, until: float) -> None:
        """Serve until ``until`` on the schedule's clock (and the tick
        running then has ended)."""
        open_loop = self.mix["kind"] == "open_loop"
        while True:
            now = time.perf_counter() - self.origin
            if now >= until:
                return
            with self.ann("client"):
                while open_loop and self.next < len(self.reqs) and \
                        self.reqs[self.next].due <= now:
                    self.submit(now)
            with self.ann("admit"):
                self.admit(now)
            if not self.engine.sched.busy:
                nxt = self.reqs[self.next].due if open_loop and \
                    self.next < len(self.reqs) else until
                with self.ann("wait"):
                    time.sleep(max(0.0, min(nxt, until) - now))
                continue
            with self.ann("step"):
                self.step()

    def warm(self) -> None:
        """Serve the part of the schedule before the window (set-up)."""
        self.origin = time.perf_counter() + self.mix.get("warm_s", 0.0)
        self.ticks.clear()
        self._loop(0.0)

    def run(self, seconds: float) -> float:
        """Serve the window; returns its length in seconds."""
        self.ticks.clear()
        self.prefills.clear()
        self.w0 = time.perf_counter() - self.origin
        with self.ann(WINDOW):
            self._loop(self.w0 + seconds)
            self.w1 = time.perf_counter() - self.origin
        return self.w1 - self.w0

    def in_window(self, t: float) -> bool:
        return self.w0 <= t <= self.w1


def preadmit(win: Window, clients: int) -> None:
    for _ in range(clients):
        win.submit(0.0)
    win.admit(0.0)


def due_in_window(win: Window) -> list:
    return [r for r in win.sent if win.in_window(r.due)]


def end_to_end(win: Window) -> dict:
    length = win.w1 - win.w0
    toks = [t for r in win.sent for t in r.token_times if win.in_window(t)]
    out = {"output_tok_s": len(toks) / length}
    gaps = [b - a for r in win.sent for a, b in zip(r.token_times,
                                                    r.token_times[1:])
            if win.in_window(a)]
    if gaps:
        out["itl_p95_ms"] = 1e3 * p95(gaps)
    if win.mix["kind"] == "open_loop":
        out["ttft_p95_s"] = p95([(r.token_times[0] if r.token_times
                                  else win.w1) - r.due
                                 for r in due_in_window(win)])
    return out


def queue_waits(win: Window) -> list:
    """Due time to admission of each request due in the window (one not
    admitted by the end counts its wait so far)."""
    return [(r.admitted if not math.isnan(r.admitted) else win.w1) - r.due
            for r in due_in_window(win)]


def sample_finished(win: Window, seed: int, want_tokens: int) -> list:
    """Finished requests drawn from the seed, the longest among them,
    until ``want_tokens`` served tokens are in."""
    done = [r for r in win.sent if r.handle.status == "done"]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.handle.out_tokens))
    rest = [r for r in done if r is not longest]
    order = traffic.rng_for(seed, 4).permutation(len(rest))
    picked, n = [longest], len(longest.handle.out_tokens)
    for i in order:
        if n >= want_tokens:
            break
        picked.append(rest[i])
        n += len(rest[i].handle.out_tokens)
    return picked


def setup(cell, seed: int, devices=None):
    """Weights, engine, cache, warm-up, and the part of the schedule
    before the window, on ``devices`` (the first chips the cell asks for
    by default); returns (engine, window)."""
    import jax
    from repro.models import build_model
    from repro.serve.engine import ServeEngine
    conf, mix = cell.config, cell.traffic
    if devices is None:
        devices = jax.devices()[:cell.workload["chips"]]
    cfg, scfg = model_config(conf), serve_config(conf)
    weights.check_layout(conf, build_model(cfg))
    backend = build_backend(cfg, scfg, weights.make_weights(conf, seed),
                            conf, devices)
    # the engine keeps the backend's placed weights, not a second copy
    engine = ServeEngine(cfg, scfg, backend.params, backend=backend)
    warm = traffic.rng_for(seed, 5).integers(0, cfg.vocab_size, 20)
    engine.submit(warm.astype(np.int32), max_new_tokens=2)
    while engine.sched.busy:
        engine._admit()
        engine.step()
    win = Window(engine, mix, traffic.requests(mix, seed, cfg.vocab_size))
    if mix["kind"] == "closed_loop":
        preadmit(win, mix["clients"])
    win.warm()
    jax.block_until_ready(engine.cache)
    return engine, win


def check(cell, seed: int, picked: list) -> dict:
    """The reference's widest gap below its best logit over the served
    tokens of ``picked``; runs after the program's state is freed."""
    if not picked:
        return {"tokens": 0, "max_gap": math.inf}
    w = weights.make_weights(cell.config, seed)
    seqs = [np.concatenate([r.prompt, np.asarray(r.handle.out_tokens,
                                                 np.int32)]) for r in picked]
    out = reference.served_gaps(cell.config, w, seqs,
                                [len(r.prompt) for r in picked])
    out["requests"] = len(picked)
    return out


def free(engine) -> None:
    engine.backend.cache = None
    engine.backend.params = None
    engine._params = None
    gc.collect()
