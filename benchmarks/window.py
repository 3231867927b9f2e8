"""The after-iteration callback that closes warm-up and the timed window
inside one ``lgb.train`` call (the idiom of ``chip_smoke.Stamps``).

Phases, all in the one call: ``warmup`` iterations (set-up ends when the last
of them is done on the device), then the timed window, which ends at the first
iteration boundary at or after ``seconds`` with ``block_until_ready`` and a
value fetch, then, in a traced run, ``trace_iters`` more iterations under the
profiler, outside the timed window so that neither disturbs the other.

Inside the window the callback blocks on the *previous* iteration, not the
current one: at most one iteration is in flight behind the host, and the
loop's own overlap of host and device stays. The program donates its score
buffer to the next iteration, so what is kept to block on is a one-element
slice of the scores, taken at each boundary (one tiny program, compiled during
warm-up).
"""
from __future__ import annotations

import time
from typing import Callable, List, Optional

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CALLBACK_SPAN = "bench.callback"
LOOP_SPAN = "bench.loop"  # from each return of the callback to its next entry
# what the host was doing in an idle gap of the device, by the span it lies in
HOST_SPANS = {CALLBACK_SPAN: "in the benchmark's callback",
              LOOP_SPAN: "in the boosting loop between callbacks"}


class Window:
    order = 100
    before_iteration = False

    def __init__(self, warmup: int, seconds: float, stop_exception: type,
                 observe_warmup: Optional[Callable[[int, object], None]] = None,
                 trace_dir: Optional[str] = None, trace_iters: int = 2) -> None:
        self.warmup = warmup
        self.seconds = seconds
        self.stop_exception = stop_exception
        self.observe_warmup = observe_warmup
        self.trace_dir = trace_dir
        self.trace_iters = trace_iters
        self.t_warm = self.t_block = self.t_fetch = None
        self.iterations = 0          # completed inside the timed window
        self.traced_iterations = 0   # completed under the profiler
        self.compiles_in_window = 0
        self.host_gaps_s: List[float] = []   # callback return -> next entry
        self.entries_s: List[float] = []     # callback entries, from the window's start
        self._prev = None
        self._mark = None
        self._t_return = None
        self._counting = False
        self._trace_started_at = None  # iterations done when the trace began
        self._loop_span = None

    # jax.monitoring listener: any executable built or loaded while counting
    def on_event(self, event: str, duration: float, **kwargs) -> None:
        if self._counting and event == COMPILE_EVENT:
            self.compiles_in_window += 1

    def __call__(self, env) -> None:
        import jax

        t_enter = time.perf_counter()
        if self._loop_span is not None:
            self._loop_span.__exit__(None, None, None)
            self._loop_span = None
        done = env.iteration - env.begin_iteration + 1
        scores = env.model._gbdt.scores
        if self._mark is None:
            self._mark = jax.jit(lambda s: s.reshape(-1)[:1])
        if self._counting and self._t_return is not None:
            self.host_gaps_s.append(t_enter - self._t_return)
            self.entries_s.append(t_enter - self.t_warm)
        with jax.profiler.TraceAnnotation(CALLBACK_SPAN):
            if done <= self.warmup:
                if self.observe_warmup is not None:
                    self.observe_warmup(done, scores)
                if done == self.warmup:
                    jax.block_until_ready(scores)
                    float(self._mark(scores)[0])  # the fetch that closes the window
                    self._counting = True
                    self.t_warm = time.perf_counter()
            elif self.t_fetch is None:
                jax.block_until_ready(self._prev)
                if time.perf_counter() - self.t_warm >= self.seconds:
                    jax.block_until_ready(scores)
                    self.t_block = time.perf_counter()
                    float(self._mark(scores)[0])
                    self.t_fetch = time.perf_counter()
                    self.iterations = done - self.warmup
                    if self.trace_dir is None:
                        self._counting = False
                        raise self.stop_exception(env.iteration, [])
                    self._start_trace()
                    self._trace_started_at = done
            else:
                jax.block_until_ready(self._prev)
                if done - self._trace_started_at >= self.trace_iters:
                    jax.block_until_ready(scores)
                    self.traced_iterations = done - self._trace_started_at
                    self._counting = False
                    jax.profiler.stop_trace()
                    raise self.stop_exception(env.iteration, [])
            self._prev = self._mark(scores)
        self._loop_span = jax.profiler.TraceAnnotation(LOOP_SPAN)
        self._loop_span.__enter__()
        self._t_return = time.perf_counter()

    def _start_trace(self) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # host spans are the benchmark's own
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)

    @property
    def window_s(self) -> float:
        return self.t_fetch - self.t_warm
