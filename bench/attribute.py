#!/usr/bin/env python3
"""Where the chip's idle time goes in a traced run of one cell.

    python3 bench/attribute.py --workload internlm2-1.8b.local8 --seed 7 \\
        --seconds 20 [--record bench/tests/data/trace_local8_save.json \\
        --step 8]

Runs the cell as ``bench/run.py --trace 1`` does and reads its trace with
the program's spans (``bench/program_trace.py``).  The last line of
standard output is the run's result line with three more keys:
``named_gaps``, the window's longest idle stretches of the first chip
(leading and trailing idle included), each named by the main thread's
innermost span and the checkpoint writer's open at its middle;
``span_ms``, the mean duration of each span; and ``writers``, each
``vc.writer`` span's step, bytes and seconds.  With ``--record`` the round
that saves checkpoint ``--step`` and the round after it are written as a
recorded trace for the tests: ops of 0.2 ms or more, their text cut to 160
characters, and every span with its args, on a clock that starts with the
save round.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import program_trace as PT  # noqa: E402
from bench import run  # noqa: E402
from bench import trace as T  # noqa: E402

MIN_OP_S = 2e-4
OP_TEXT = 160


def cut(tr: PT.ProgramTrace, step: int) -> PT.ProgramTrace:
    """The round that saves checkpoint ``step`` and the round after it."""
    rounds = {s.args["round"]: s for s in tr.named("vc.round")}
    lo, hi = rounds[step - 1].start, rounds[step].end
    return PT.ProgramTrace(
        ops={d: [T.Op(o.text[:OP_TEXT], o.start - lo, o.dur) for o in ops
                 if o.dur >= MIN_OP_S and o.end > lo and o.start < hi]
             for d, ops in tr.ops.items()},
        spans=[PT.Span(s.name, s.start - lo, s.dur, s.args)
               for s in tr.spans if s.end > lo and s.start < hi])


def summary(tr: PT.ProgramTrace) -> dict:
    names = sorted({s.name for s in tr.spans})
    return {"named_gaps": tr.top_gaps(12),
            "span_ms": {n: 1e3 * sum(tr.span_seconds(n))
                        / len(tr.span_seconds(n)) for n in names},
            "writers": [[s.args.get("step"), s.args.get("bytes"), s.dur]
                        for s in tr.named(PT.WRITER)]}


def attribute(cell: run.Cell, seed: int, seconds: float, *, devices,
              t_start: float, record: Path = None, step: int = 0) -> dict:
    work = PT.WORK_DIR / cell.name
    try:
        result = run.run_cell(cell, seed, seconds, True, devices=devices,
                              t_start=t_start, work=work)
        tr = PT.load(work / "trace")
    finally:
        shutil.rmtree(work, ignore_errors=True)
    result.update(summary(tr))
    if record is not None:
        rec = cut(tr, step)
        record.write_text(json.dumps(rec.to_json()))
        result["recorded"] = {"bytes": record.stat().st_size,
                              **summary(rec)}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--record", type=Path, default=None)
    ap.add_argument("--step", type=int, default=0,
                    help="with --record: the checkpoint whose save round "
                         "is recorded")
    args = ap.parse_args(argv)
    if args.record and args.step < 1:
        ap.error("--record needs --step, a checkpoint the window saves")
    cell = run.load_cell(args.workload)
    try:
        devices = run.use_devices(cell.chips)
    except run.NoChip as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 2
    run.enable_compile_cache()
    sys.path.insert(0, str(ROOT / "src"))
    result = attribute(cell, args.seed, args.seconds, devices=devices,
                       t_start=T_START, record=args.record, step=args.step)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
