"""Reduction of a profiler trace to what the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
two things: every XLA op that ran on a TPU (device plane
``/device:TPU:<n>``, line ``XLA Ops``), named by its HLO instruction text
(``%fusion.460 = f32[1,173228032]{...} fusion(...)``: the TPU's op events
carry no JAX name stack), and the host spans that the benchmark itself
opened (``bench.*``, on the host plane's ``python3`` line).  Times are
seconds on the trace's own clock, which host and device planes share.
``Trace.to_json`` / ``from_json`` keep a recorded trace small enough to
check in as a test input.
"""
from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
SHAPE = re.compile(r"\[([0-9,]+)\]")
COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "collective-broadcast")


@dataclass(frozen=True)
class Op:
    text: str          # the HLO instruction as the trace names the op
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur

    @property
    def name(self) -> str:
        """``fusion.460``: the instruction's name without its text."""
        return self.text.split(" = ", 1)[0].lstrip("%")

    @property
    def largest_dim(self) -> int:
        """The largest dimension of any shape in the instruction (result
        and operands)."""
        return max((int(x) for g in SHAPE.findall(self.text)
                    for x in g.split(",") if x), default=0)

    @property
    def container(self) -> bool:
        """A while loop: its event spans the body's ops, listed apart."""
        return self.name.startswith("while")

    @property
    def collective(self) -> bool:
        return any(self.name.startswith(c) for c in COLLECTIVES)


@dataclass(frozen=True)
class Span:
    name: str
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Trace:
    ops: Dict[int, List[Op]] = field(default_factory=dict)
    spans: List[Span] = field(default_factory=list)

    # -- reductions -------------------------------------------------------
    def busy(self, device: int) -> List[Tuple[float, float]]:
        """Union of the device's op intervals, merged and sorted."""
        out: List[List[float]] = []
        for op in sorted(self.ops.get(device, []), key=lambda o: o.start):
            if out and op.start <= out[-1][1]:
                out[-1][1] = max(out[-1][1], op.end)
            else:
                out.append([op.start, op.end])
        return [(a, b) for a, b in out]

    def busy_s(self, device: int) -> float:
        return sum(b - a for a, b in self.busy(device))

    def mean_busy_s(self) -> Optional[float]:
        if not self.ops:
            return None
        return sum(self.busy_s(d) for d in self.ops) / len(self.ops)

    def idle_gaps(self, device: int) -> List[Tuple[float, float]]:
        iv = self.busy(device)
        return [(a[1], b[0]) for a, b in zip(iv, iv[1:]) if b[0] > a[1]]

    def span_at(self, t: float) -> str:
        """Name of the innermost benchmark span open at host time ``t``."""
        best = None
        for s in self.spans:
            if s.start <= t <= s.end and (best is None or s.dur < best.dur):
                best = s
        return best.name if best else "host: trainer loop"

    def op_seconds(self, pred) -> Dict[int, float]:
        """Per device, the summed duration of the ops ``pred`` accepts."""
        return {d: sum(o.dur for o in ops if pred(o))
                for d, ops in self.ops.items()}

    def top_ops(self, k: int = 10) -> List[List]:
        """The ops that took most device time, summed over their runs and
        averaged over the chips (loops left out: their bodies count)."""
        tot: Dict[str, float] = {}
        for ops in self.ops.values():
            for o in ops:
                if not o.container:
                    tot[o.name] = tot.get(o.name, 0.0) + o.dur
        n = max(len(self.ops), 1)
        return [[name, s / n] for name, s in
                sorted(tot.items(), key=lambda kv: -kv[1])[:k]]

    def top_gaps(self, k: int = 10) -> List[List]:
        """The longest idle gaps of device 0, each named by the host span
        open at its middle."""
        if not self.ops:
            return []
        dev = min(self.ops)
        gaps = sorted(self.idle_gaps(dev), key=lambda g: g[0] - g[1])[:k]
        return [[self.span_at((a + b) / 2), b - a] for a, b in gaps]

    def span_seconds(self, name: str) -> List[float]:
        return [s.dur for s in self.spans if s.name == name]

    # -- recorded traces ----------------------------------------------------
    def to_json(self) -> dict:
        return {"ops": {str(d): [[o.text, o.start, o.dur]
                                 for o in ops] for d, ops in self.ops.items()},
                "spans": [[s.name, s.start, s.dur] for s in self.spans]}

    @classmethod
    def from_json(cls, raw: dict) -> "Trace":
        return cls(ops={int(d): [Op(*o) for o in ops]
                        for d, ops in raw["ops"].items()},
                   spans=[Span(*s) for s in raw["spans"]])


def load(directory: Path) -> Trace:
    """Read the newest ``.xplane.pb`` under ``directory``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(str(Path(directory) / "plugins" / "profile"
                                 / "*" / "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no trace under {directory}")
    data = ProfileData.from_file(files[-1])
    tr = Trace()
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                ops = tr.ops.setdefault(int(m.group(1)), [])
                for e in line.events:
                    ops.append(Op(e.name, e.start_ns * 1e-9,
                                  e.duration_ns * 1e-9))
            elif not m:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        tr.spans.append(Span(e.name, e.start_ns * 1e-9,
                                             e.duration_ns * 1e-9))
    return tr
