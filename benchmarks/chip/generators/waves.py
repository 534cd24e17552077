"""Offline batch generation in waves.

Each wave takes `requests_per_wave` prompts of `prompt_len` tokens drawn from
the seed, prefills them together into a fresh serving state with every
reuse-cache lane reset, and decodes `answer_len` tokens greedily through the
shared decode step, as `serve` does a step: tokens uploaded, the decode step,
greedy sampling on the device, one host pull. Waves run back to back until
the window closes. Every prompt of a wave has one length because the decode
state keeps one position for all lanes.

A token counts at the moment the host holds it. The gap before a request's
token is the time since its previous token. The spans below mark wave
set-up, prefill, each decode step and each host pull in the profiler's trace.
"""

from __future__ import annotations

import dataclasses
import time

import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from chip.check import Served


@dataclasses.dataclass
class Step:
    kind: str          # "prefill" | "decode"
    start: float       # host clock, seconds
    end: float
    rows: int          # tokens emitted (one per request)
    position: int      # position of the first token the step feeds


@dataclasses.dataclass
class Window:
    start: float
    seconds: float
    steps: list            # every Step the window began, in order
    gaps_s: list           # token gaps inside the window, seconds
    tokens: int            # tokens emitted inside the window
    attempted: int         # requests whose wave began inside the window
    requests: list         # Served, for the check (filled by `drain`)


@dataclasses.dataclass
class Flight:
    """The wave being decoded."""

    prompts: np.ndarray    # [B, P]
    out: list              # [B] token arrays emitted so far, one per step
    cur: np.ndarray        # [B, 1] the tokens the next step feeds


class Generator:
    """Drives a `system.System` with one traffic mix of kind `waves`."""

    WARM_UP_WAVE = 2**32 - 1    # a wave index no window reaches

    def __init__(self, system, traffic: dict, seed: int):
        self.sys = system
        self.t = traffic
        self.seed = int(seed) % 2**64
        self.state = None
        self.wave = 0
        self.n = 0
        self.flight = None
        self.done_requests = []

    def _prompts(self, wave: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, 0x5A7E, wave])
        return rng.integers(0, self.sys.cfg.vocab,
                            (self.t["requests_per_wave"], self.t["prompt_len"]),
                            dtype=np.int32)

    def _begin(self, prompts: np.ndarray):
        """Fresh state, reset lanes, prefill, first tokens on the host."""
        s = self.sys
        with TraceAnnotation("bench:wave_setup"):
            self.state = None
            self.state = s.new_state()
            if s.rcache is not None:
                s.rcache = s.reset_lanes(s.rcache)
            dev = jnp.asarray(prompts)
        with TraceAnnotation("bench:prefill"):
            logits, self.state = s.prefill(s.params, dev, self.state)
            nxt = s.sample(logits)
            with TraceAnnotation("bench:pull"):
                return np.asarray(nxt).reshape(-1, 1)

    def _step(self, cur: np.ndarray, n: int) -> np.ndarray:
        s = self.sys
        with StepTraceAnnotation("bench:decode", step_num=n):
            logits, self.state, s.rcache = s.decode(
                s.params, jnp.asarray(cur), self.state, s.rcache)
            nxt = s.sample(logits)
            with TraceAnnotation("bench:pull"):
                return np.asarray(nxt).reshape(-1, 1)

    def warm_up(self) -> None:
        """Every program the window runs, once: a prefill, two decode steps,
        the lane reset and the sampler."""
        cur = self._begin(self._prompts(self.WARM_UP_WAVE))
        for n in range(2):
            cur = self._step(cur, n)
        self.state = None

    def run(self, seconds: float) -> Window:
        """Waves back to back until `seconds` have passed. A wave that the
        close cuts stays in flight for `drain`."""
        p = self.t["prompt_len"]
        steps, gaps = [], []
        tokens = attempted = 0
        start = time.perf_counter()
        close = start + seconds
        self.done_requests = []
        while time.perf_counter() < close:
            prompts = self._prompts(self.wave)
            self.wave += 1
            attempted += len(prompts)
            t0 = time.perf_counter()
            cur = self._begin(prompts)
            t1 = time.perf_counter()
            steps.append(Step("prefill", t0, t1, len(prompts), 0))
            if t1 <= close:
                tokens += len(prompts)
            self.flight = Flight(prompts, [cur[:, 0]], cur)
            last = t1
            while len(self.flight.out) < self.t["answer_len"]:
                if time.perf_counter() >= close:
                    break
                s0 = time.perf_counter()
                cur = self._next()
                s1 = time.perf_counter()
                steps.append(Step("decode", s0, s1, len(prompts),
                                  p + len(self.flight.out) - 2))
                if s1 <= close:
                    tokens += len(prompts)
                    gaps.extend([s1 - last] * len(prompts))
                last = s1
            else:
                self._retire()
        return Window(start=start, seconds=seconds, steps=steps, gaps_s=gaps,
                      tokens=tokens, attempted=attempted, requests=[])

    def _next(self) -> np.ndarray:
        f = self.flight
        f.cur = self._step(f.cur, self.n)
        self.n += 1
        f.out.append(f.cur[:, 0])
        return f.cur

    def _retire(self, finished: bool = True) -> None:
        served = np.stack(self.flight.out, axis=1)
        self.done_requests += [Served(pr, served[i], finished)
                               for i, pr in enumerate(self.flight.prompts)]
        self.flight = None

    def drain(self, window: Window) -> None:
        """After the close: finish the wave in flight when no request has
        finished yet, so the check has whole requests, and hand every
        request to the window's record. Nothing here is timed."""
        if self.flight is not None:
            if not self.done_requests:
                while len(self.flight.out) < self.t["answer_len"]:
                    self._next()
                self._retire()
            else:
                self._retire(finished=False)
        window.requests = self.done_requests
        self.state = None
