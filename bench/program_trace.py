"""The program's own spans in a profiler trace, and what they let a reader
say about the device's idle time.

The program opens host spans named ``vc.*`` (``src/repro/obs.py``): the
round loop's ``vc.round`` (a step span with args ``round``, ``compiles``
and ``compiled``), ``vc.batches``, ``vc.dispatch``, ``vc.loss_wait``,
``vc.ckpt.wait``, the checkpoint save's ``vc.ckpt.save`` with
``vc.ckpt.join`` and ``vc.ckpt.to_host``, and on the writer thread
``vc.writer`` (args ``step`` and ``bytes``, the file's size) with
``vc.writer.serialize``, ``vc.writer.write`` and ``vc.writer.retain``.
``bench/trace.py`` keeps only the benchmark's ``bench.*`` spans, without
their args; ``load`` and ``of`` add the program's, with their args.

The profiler puts the writer thread's spans on the same host line as the
main thread's, so they are told apart by name: a ``vc.writer*`` span is the
writer's, every other span the main thread's.  The window on the trace's
clock runs from the start of the first ``vc.round`` to the end of
``vc.ckpt.wait`` (or of the last ``vc.round``).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from bench import trace as T

PREFIX = "vc."
WRITER = "vc.writer"
# where bench/run.py's main() has a cell's run write its trace
WORK_DIR = Path(__file__).resolve().parent / ".work"


@dataclass(frozen=True)
class Span(T.Span):
    args: Dict = field(default_factory=dict)


def _innermost(spans, t: float) -> Optional[Span]:
    best = None
    for s in spans:
        if s.start <= t <= s.end and (best is None or s.dur < best.dur):
            best = s
    return best


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


@dataclass
class ProgramTrace(T.Trace):
    """A ``bench.trace.Trace`` whose spans include the program's."""

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def window(self) -> Optional[Tuple[float, float]]:
        rounds = self.named("vc.round")
        if not rounds:
            return None
        return (min(s.start for s in rounds),
                max(s.end for s in rounds + self.named("vc.ckpt.wait")))

    def idle(self, device: int, lo: float, hi: float
             ) -> List[Tuple[float, float]]:
        """The complement of the device's busy intervals within
        ``[lo, hi]``: leading and trailing idle included."""
        out, t = [], lo
        for a, b in self.busy(device):
            if a >= hi:
                break
            if a > t:
                out.append((t, a))
            t = max(t, b)
        if t < hi:
            out.append((t, hi))
        return out

    def writer_idle_s(self, device: int) -> float:
        """Seconds of the window in which the device was idle while the
        checkpoint writer thread ran (inside a ``vc.writer`` span)."""
        lo, hi = self.window()
        writers = _union([(max(s.start, lo), min(s.end, hi))
                          for s in self.named(WRITER)
                          if s.end > lo and s.start < hi])
        return sum(max(0.0, min(b, d) - max(a, c))
                   for a, b in self.idle(device, lo, hi)
                   for c, d in writers)

    def span_at(self, t: float) -> str:
        """The main thread's innermost span open at ``t`` and, where the
        writer thread has one open, ``" + "`` its innermost."""
        main = _innermost((s for s in self.spans
                           if not s.name.startswith(WRITER)), t)
        name = main.name if main else "host: trainer loop"
        w = _innermost((s for s in self.spans
                        if s.name.startswith(WRITER)), t)
        return f"{name} + {w.name}" if w else name

    def top_gaps(self, k: int = 10) -> List[List]:
        """The longest idle stretches of device 0 in the window, leading
        and trailing idle included, each named at its middle."""
        win = self.window()
        if not self.ops or win is None:
            return super().top_gaps(k)
        gaps = sorted(self.idle(min(self.ops), *win),
                      key=lambda g: g[0] - g[1])[:k]
        return [[self.span_at((a + b) / 2), b - a] for a, b in gaps]

    # -- recorded traces ----------------------------------------------------
    def to_json(self) -> dict:
        raw = super().to_json()
        raw["spans"] = [[s.name, s.start, s.dur]
                        + ([s.args] if getattr(s, "args", None) else [])
                        for s in self.spans]
        return raw

    @classmethod
    def from_json(cls, raw: dict) -> "ProgramTrace":
        ops = T.Trace.from_json({"ops": raw["ops"], "spans": []}).ops
        return cls(ops=ops, spans=[Span(*s) for s in raw["spans"]])


def newest(directory: Path) -> Optional[Path]:
    """The newest ``.xplane.pb`` under a profiler's log directory."""
    files = sorted(Path(directory).glob("plugins/profile/*/*.xplane.pb"))
    return files[-1] if files else None


@functools.lru_cache(maxsize=1)
def _program_spans(path: str, _mtime_ns: int) -> Tuple[Span, ...]:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if T.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append(Span(e.name, e.start_ns * 1e-9,
                                    e.duration_ns * 1e-9,
                                    {k: v for k, v in e.stats
                                     if not k.startswith("_")}))
    return tuple(out)


def program_spans(path: Path) -> List[Span]:
    """The ``vc.*`` spans of the trace file ``path``, with their args."""
    return list(_program_spans(str(path), path.stat().st_mtime_ns))


def _with_program(tr: T.Trace, path: Path) -> ProgramTrace:
    return ProgramTrace(ops=tr.ops,
                        spans=[Span(s.name, s.start, s.dur)
                               for s in tr.spans] + program_spans(path))


def load(directory: Path) -> ProgramTrace:
    """The newest trace under ``directory``, the program's spans
    included."""
    path = newest(directory)
    if path is None:
        raise FileNotFoundError(f"no trace under {directory}")
    return _with_program(T.load(directory), path)


def of(ctx) -> Optional[ProgramTrace]:
    """The trace a per-layer reader's ``ctx`` was read from, the program's
    spans included: ``ctx.trace`` itself where it is a ``ProgramTrace``,
    else read again from where ``bench/run.py`` has a cell write it.  None
    where there is no such trace or the program opened no ``vc.*`` span
    (a program without ``repro.obs``)."""
    if isinstance(ctx.trace, ProgramTrace):
        return ctx.trace
    path = newest(WORK_DIR / ctx.cell.name / "trace")
    if path is None or not program_spans(path):
        return None
    return _with_program(ctx.trace, path)
