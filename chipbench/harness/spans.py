"""Host spans around the system's layers, and JAX's compile events.

The benchmark records spans from its own files: it wraps the module
attributes that the SoA path calls through (``module:attr`` targets
that the per-layer metric files name) and times each call on the host
clock.  Each span is also a ``jax.profiler.TraceAnnotation`` named
``chipbench.<span>``, so a profiler trace shows what the host was doing
in every gap of the device.  A target that is gone is skipped: the
metrics that read its span are then absent.
"""
from __future__ import annotations

import contextlib
import importlib
import time
from typing import Callable, Dict, List, Tuple

ANNOTATION_PREFIX = "chipbench."


class SpanRecorder:
    """``(name, start_s, end_s)`` of every wrapped call, host clock."""

    def __init__(self) -> None:
        import jax

        self._annotation = jax.profiler.TraceAnnotation
        self.spans: List[Tuple[str, float, float]] = []
        self._undo: List[Callable[[], None]] = []

    def wrap(self, name: str, fn: Callable) -> Callable:
        def wrapped(*args, **kwargs):
            with self.span(name):
                return fn(*args, **kwargs)

        return wrapped

    def install(self, hooks: Dict[str, str]) -> None:
        """Wrap each ``module:attr`` target under its span name."""
        for name, target in hooks.items():
            mod_name, _, attr = target.partition(":")
            try:
                mod = importlib.import_module(mod_name)
                fn = getattr(mod, attr)
            except (ImportError, AttributeError):
                continue
            if not callable(fn):
                continue
            setattr(mod, attr, self.wrap(name, fn))
            self._undo.append(lambda m=mod, a=attr, f=fn: setattr(m, a, f))

    def uninstall(self) -> None:
        while self._undo:
            self._undo.pop()()

    @contextlib.contextmanager
    def span(self, name: str):
        """Records one span of the harness itself."""
        t0 = time.perf_counter()
        try:
            with self._annotation(ANNOTATION_PREFIX + name):
                yield
        finally:
            self.spans.append((name, t0, time.perf_counter()))

    def totals(self, t0: float, t1: float) -> Dict[str, Tuple[int, float]]:
        """``{name: (count, seconds)}`` of spans that began in [t0, t1)."""
        out: Dict[str, Tuple[int, float]] = {}
        for name, a, b in self.spans:
            if t0 <= a < t1:
                n, s = out.get(name, (0, 0.0))
                out[name] = (n + 1, s + (b - a))
        return out


class CompileCounter:
    """Counts XLA executables built (compiled or loaded from the
    persistent cache) and persistent-cache hits, from JAX's own
    monitoring events (as the system's ``chip_smoke.py`` counts them)."""

    def __init__(self) -> None:
        from jax import monitoring

        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> Tuple[int, float, int]:
        return self.n, self.seconds, self.cache_hits
