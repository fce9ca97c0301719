"""Host spans and the compile counter, written into the profiler's trace.

Spans are ``jax.profiler`` trace annotations, so they land on the same
clock as the device's op events of a ``jax.profiler`` trace.  With the
profiler off a span costs one activity check.  Every span name starts
with ``vc.``; spans of the round loop carry ``round=<r>``, those of the
checkpoint layer ``step=<s>`` of the save that caused them.  A span's
``set_metadata(**args)`` adds args known only at its end.
"""
from __future__ import annotations

import contextlib
import threading

import jax
from jax.profiler import StepTraceAnnotation, TraceAnnotation

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def span(name: str, **args) -> TraceAnnotation:
    """A host span ``name`` with ``args`` as its event stats."""
    return TraceAnnotation(name, **args)


@contextlib.contextmanager
def step_span(name: str, round: int):
    """A span that the profiler's step view counts as step ``round``, with
    args ``compiles`` (backend compiles and cache loads during it) and
    ``compiled`` (the last one's ``fun_name``, where there was one)."""
    c = compiles()
    n0 = c.count
    with StepTraceAnnotation(name, step_num=round, round=round) as step:
        yield step
        n = c.count - n0
        step.set_metadata(compiles=n, **({"compiled": c.last} if n else {}))


class _Compiles:
    """Backend compiles and persistent-cache loads in this process (the
    event wraps ``compile_or_get_cached``), and the ``fun_name`` of the
    last one."""

    def __init__(self):
        self.count, self.last = 0, ""
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **kw) -> None:
        if event == COMPILE_EVENT:
            with self._lock:
                self.count += 1
                self.last = kw.get("fun_name", "")


_compiles = None
_install = threading.Lock()


def compiles() -> _Compiles:
    """The process's compile counter, listening from its first call."""
    global _compiles
    with _install:
        if _compiles is None:
            _compiles = _Compiles()
    return _compiles
