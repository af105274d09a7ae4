"""The measured window: one `FedAvgAPI.train()` call with `comm_round` out of
reach and a tracer of the benchmark's own. The tracer opens the window after
`warm_rounds` rounds, closes it at the first round boundary past the
deadline and ends the drive by raising from `round()`; `train()`'s own
`finally` closes the prefetcher. Both ends come after a `block_until_ready`
on the global model, so the window holds all the work of rounds
[first, last) and nothing else. Nothing in the program is edited.

Also here, copied from chip_smoke.py: `CompileLog` and `peak_bytes`.
"""

from __future__ import annotations

import contextlib
import time

import jax

from fedml_tpu import telemetry

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class WindowOver(Exception):
    """Raised by WindowTracer.round() when the window has closed."""


class CompileLog:
    """(end_time, seconds) of every backend compile — cache hit or miss —
    while open, on the tracer's clock (time.perf_counter)."""

    def __enter__(self):
        self.compiles: list[tuple[float, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.compiles.append((time.perf_counter(), duration))

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)

    def inside(self, t0: float, t1: float) -> int:
        """Compiles that ENDED in (t0, t1]."""
        return sum(1 for t, _ in self.compiles if t0 < t <= t1)


def peak_bytes() -> int | None:
    """peak_bytes_in_use of the fullest device so far (None where the backend
    reports no memory stats — the CPU the tests run on)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class WindowTracer(telemetry.Tracer):
    """`api` is set after FedAvgAPI(...) is built. With `trace_dir`, a
    jax.profiler trace of about `trace_seconds` is taken inside the window,
    from `trace_after` seconds after it opens to the next round boundary past
    its length, and every host span is also written into the profiler's
    trace as a TraceAnnotation, so idle gaps can be named by what the host
    was doing."""

    def __init__(self, warm_rounds: int, seconds: float,
                 trace_dir: str | None = None, trace_seconds: float = 3.0,
                 trace_after: float = 1.0):
        super().__init__()
        self.api = None
        self.warm_rounds, self.seconds = warm_rounds, seconds
        self.t_open = self.t_close = self.first = self.last = None
        self.trace_dir = trace_dir
        self.trace_seconds, self.trace_after = trace_seconds, trace_after
        self.t_trace0 = self.t_trace1 = None
        self.trace_rounds = (None, None)
        self.paused_s = 0.0   # inside start_trace/stop_trace, device drained

    def _profiler(self, call, *args) -> None:
        self._drain()
        t0 = self.now()
        call(*args)
        self.paused_s += self.now() - t0

    def _drain(self) -> float:
        jax.block_until_ready(self.api.global_variables)
        return self.now()

    def _trace_edge(self, round_idx: int) -> None:
        since = self.now() - self.t_open - self.paused_s
        if self.t_trace0 is None and since >= self.trace_after:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0   # it slows the host it traces
            options.enable_hlo_proto = False
            self._profiler(jax.profiler.start_trace, self.trace_dir, False,
                           False, options)
            self.t_trace0 = self.now()
            self.trace_rounds = (round_idx, None)
        elif (self.t_trace0 is not None and self.t_trace1 is None
              and self.now() - self.t_trace0 >= self.trace_seconds):
            self._stop_trace(round_idx)

    def _stop_trace(self, round_idx: int) -> None:
        self.t_trace1 = self._drain()
        self._profiler(jax.profiler.stop_trace)
        self.trace_rounds = (self.trace_rounds[0], round_idx)

    @contextlib.contextmanager
    def span(self, name, round_idx=None, **attrs):
        if self.t_trace0 is not None and self.t_trace1 is None:
            with jax.profiler.TraceAnnotation(f"host:{name}"):
                with super().span(name, round_idx, **attrs) as h:
                    yield h
        else:
            with super().span(name, round_idx, **attrs) as h:
                yield h

    @contextlib.contextmanager
    def round(self, round_idx):
        if round_idx == self.warm_rounds:   # warm-up over: drain, open
            self.t_open, self.first = self._drain(), round_idx
        if self.t_open is not None:
            if self.now() - self.t_open - self.paused_s >= self.seconds:
                if self.t_trace0 is not None and self.t_trace1 is None:
                    self._stop_trace(round_idx)
                self.t_close, self.last = self._drain(), round_idx
                raise WindowOver  # train()'s finally closes the prefetcher
            if self.trace_dir is not None:
                self._trace_edge(round_idx)
        with super().round(round_idx) as h:
            yield h

    def window_spans(self, name: str) -> list:
        """Spans called `name` of the window's rounds [first, last)."""
        return [s for s in self.spans if s["name"] == name
                and s["round"] is not None
                and self.first <= s["round"] < self.last]
