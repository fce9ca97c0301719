"""The program's own spans (``repro.obs``) as the benchmark reads them:
the spans a traced ``train()`` opens, with their args, on the main thread
and the checkpoint writer's; the readers of ``ckpt_write_ms``,
``ckpt_idle_ms`` and ``window_compiles`` on synthetic traces; and the
naming of an idle gap by the main thread's span and the writer's."""
import json
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

from bench import program_trace as PT
from bench import run, trace
from bench.metrics import ckpt_idle_ms, ckpt_write_ms, window_compiles
from bench.tests.test_bench_check import tiny_cell

DATA = Path(__file__).resolve().parent / "data"
READERS = (ckpt_write_ms, ckpt_idle_ms, window_compiles)


def S(name, start, dur, **args):
    return PT.Span(name, start, dur, args)


def op(start, dur):
    return trace.Op("%fusion.1 = f32[8] fusion(f32[8] %a)", start, dur)


def ctx_of(tr):
    return SimpleNamespace(trace=tr, cell=SimpleNamespace(name="none"))


def by_round(spans, key="round"):
    return sorted(spans, key=lambda s: s.args[key])


def inside(child, parent):
    return parent.start <= child.start and child.end <= parent.end


def test_a_traced_train_opens_every_span_with_its_args(tmp_path):
    from repro.launch import train as T
    cell = tiny_cell()
    cfg = cell.program.program_config(cell.conf)
    ckpt = tmp_path / "ckpt"
    with jax.profiler.trace(str(tmp_path / "trace")):
        T.train(cfg, ckpt_dir=str(ckpt), islands=1, rounds=3, local_steps=1,
                batch=2, seq=16, ckpt_every=1, log=lambda _: None)
    tr = PT.load(tmp_path / "trace")

    rounds = by_round(tr.named("vc.round"))
    assert [s.args["round"] for s in rounds] == [0, 1, 2]
    # train() builds a fresh jit each call: its first round compiles
    assert rounds[0].args["compiles"] > 0 and rounds[0].args["compiled"]
    assert [s.args["compiles"] for s in rounds[1:]] == [0, 0]
    assert all("compiled" not in s.args for s in rounds[1:])
    for name in ("vc.batches", "vc.dispatch", "vc.loss_wait"):
        spans = by_round(tr.named(name))
        assert [s.args["round"] for s in spans] == [0, 1, 2], name
        assert all(inside(s, r) for s, r in zip(spans, rounds)), name

    saves = by_round(tr.named("vc.ckpt.save"), "step")
    assert [s.args["step"] for s in saves] == [1, 2, 3]
    assert all(inside(s, r) for s, r in zip(saves, rounds))
    for name in ("vc.ckpt.join", "vc.ckpt.to_host"):
        spans = by_round(tr.named(name), "step")
        assert all(inside(s, p) for s, p in zip(spans, saves)), name
    host = tr.named("vc.ckpt.to_host")[0].args["bytes"]
    assert host == 4 * sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(lambda: T.build_model(cfg).init(
            jax.random.PRNGKey(0)))))

    writers = by_round(tr.named("vc.writer"), "step")
    assert [s.args["step"] for s in writers] == [1, 2, 3]
    for s in writers:
        f = ckpt / f"ckpt_{s.args['step']:08d}.msgpack"
        assert s.args["bytes"] == f.stat().st_size > host
    for name in ("vc.writer.serialize", "vc.writer.write",
                 "vc.writer.retain"):
        spans = by_round(tr.named(name), "step")
        assert [s.args["step"] for s in spans] == [1, 2, 3], name
        assert all(inside(s, w) for s, w in zip(spans, writers)), name
    [wait] = tr.named("vc.ckpt.wait")
    assert wait.args == {"step": 3} and wait.start >= rounds[-1].end


def _two_chip_trace():
    """Two chips over a window of two rounds (0-4 s) and the final wait
    (4-5 s); the writer runs 1.45-4.45 s.  Chip 0 is busy 0-1 and 2-3.2,
    chip 1 busy 0-2; both idle to the end."""
    return PT.ProgramTrace(
        ops={0: [op(0.0, 1.0), op(2.0, 1.2)], 1: [op(0.0, 2.0)]},
        spans=[S("vc.round", 0.0, 2.0, round=4, compiles=0),
               S("vc.batches", 0.0, 0.5, round=4),
               S("vc.ckpt.save", 1.1, 0.3, step=5),
               S("vc.round", 2.0, 2.0, round=5, compiles=2,
                 compiled="jit(f)"),
               S("vc.batches", 2.0, 1.5, round=5),
               S("vc.ckpt.wait", 4.0, 1.0, step=5),
               S("vc.writer", 1.45, 3.0, step=5, bytes=10),
               S("vc.writer.serialize", 1.45, 2.55, step=5),
               S("vc.writer.write", 4.0, 0.45, step=5),
               # a writer that started before the window: not averaged
               S("vc.writer", -1.0, 0.5, step=4, bytes=10)])


def test_checkpoint_readers_on_a_synthetic_trace():
    tr = _two_chip_trace()
    ctx = ctx_of(tr)
    assert tr.window() == (0.0, 5.0)
    assert tr.idle(0, 0.0, 5.0) == [(1.0, 2.0), (3.2, 5.0)]
    assert tr.idle(1, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 5.0)]
    assert ckpt_write_ms.read(ctx) == pytest.approx(3000.0)
    # chip 0: idle 1.45-2 and 3.2-4.45 inside the writer, 1.8 s; chip 1:
    # 2-4.45, 2.45 s; the trailing idle in the final wait counts
    assert tr.writer_idle_s(0) == pytest.approx(1.8)
    assert tr.writer_idle_s(1) == pytest.approx(2.45)
    assert ckpt_idle_ms.read(ctx) == pytest.approx(1e3 * 2.125 / 2)
    assert window_compiles.read(ctx) == 2.0


def test_gaps_are_named_by_the_main_threads_span_and_the_writers():
    tr = _two_chip_trace()
    assert tr.span_at(3.1) == "vc.batches + vc.writer.serialize"
    assert tr.span_at(4.2) == "vc.ckpt.wait + vc.writer.write"
    # without a writer span open the name is the main thread's alone
    assert tr.span_at(1.0) == "vc.round"
    # the trailing idle, 3.2-5 s, is a gap too
    assert tr.top_gaps(2) == [["vc.ckpt.wait + vc.writer.write",
                               pytest.approx(1.8)],
                              ["vc.round + vc.writer.serialize",
                               pytest.approx(1.0)]]


def test_a_writer_span_is_never_taken_for_the_main_threads():
    """The writer's spans share the main thread's host line; one that is
    the shortest span open is still not the main thread's."""
    tr = PT.ProgramTrace(spans=[S("vc.writer", 1.0, 2.0, step=2),
                                S("vc.writer.write", 1.5, 0.2, step=2),
                                S("bench.round_batches", 0.5, 3.0)])
    assert tr.span_at(1.6) == "bench.round_batches + vc.writer.write"
    assert tr.span_at(2.5) == "bench.round_batches + vc.writer"
    tr = PT.ProgramTrace(spans=[S("vc.writer", 1.0, 2.0, step=2)])
    assert tr.span_at(1.5) == "host: trainer loop + vc.writer"


def test_readers_find_nothing_in_a_program_without_spans():
    """A program without ``repro.obs`` (the parent of this reader) gives a
    trace with ops and ``bench.*`` spans only: every reader is silent."""
    tr = trace.Trace(ops={0: [op(0.0, 1.0)]},
                     spans=[trace.Span("bench.ckpt_save", 1.0, 0.5)])
    for reader in READERS:
        assert reader.read(ctx_of(tr)) is None
    assert all(r.read(ctx_of(PT.ProgramTrace())) is None for r in READERS)


def test_recorded_traces_keep_args_and_read_spans_without():
    tr = _two_chip_trace()
    back = PT.ProgramTrace.from_json(json.loads(json.dumps(tr.to_json())))
    assert back.spans == tr.spans and back.busy(1) == tr.busy(1)
    old = json.loads((DATA / "trace_local8_round.json").read_text())
    assert PT.ProgramTrace.from_json(old).spans[0].args == {}


def test_traced_run_reads_the_programs_spans(tmp_path):
    """``bench/run.py`` writes a cell's trace under ``.work/<cell>``; the
    readers find the program's spans there, and ``bench/attribute.py``
    names the spans and records two rounds of it.  The CPU has no device
    plane, so ``ckpt_idle_ms`` is silent and no gap is named."""
    from bench import attribute
    cell = tiny_cell()
    cell.name = "tiny-program-spans"
    cell.per_layer = [m for m in cell.per_layer
                      if m["name"] not in ("mfu", "server_roofline")]
    rec = tmp_path / "rec.json"
    r = attribute.attribute(cell, 7, 10, devices=jax.devices(),
                            t_start=time.perf_counter(), record=rec, step=3)
    assert not (PT.WORK_DIR / cell.name).exists()
    assert r["correct"] is True
    assert set(r["metrics"]) == {"ckpt_stall_ms", "batch_ms",
                                 "ckpt_write_ms", "window_compiles"}
    assert r["metrics"]["ckpt_write_ms"]["value"] > 0
    assert r["metrics"]["window_compiles"]["value"] == 0
    assert r["named_gaps"] == []
    assert {"vc.round", "vc.writer.write", "bench.ckpt_save"} <= set(
        r["span_ms"])
    # set-up round 1 saves step 2; the window's rounds 2-4 save 3-5
    assert [w[0] for w in r["writers"]] == [2, 3, 4, 5]
    assert all(w[1] > 0 and w[2] > 0 for w in r["writers"])
    back = PT.ProgramTrace.from_json(json.loads(rec.read_text()))
    assert [s.args["round"] for s in back.named("vc.round")] == [2, 3]
    assert [s.args["step"] for s in back.named("vc.ckpt.save")] == [3, 4]
    assert r["recorded"]["bytes"] == rec.stat().st_size


def test_reduction_of_a_recorded_chip_save_round():
    """internlm2-1.8b.local8 traced on a TPU v5e (``bench/attribute.py
    --record ... --step 8``): the round that saves checkpoint 8 (round 7)
    and the round after it, ops of 0.2 ms or more.  Hand counts on the
    file: the writer of step 8 runs 3.0434 s from 1.6132 s; chip 0 idles
    inside it 1.1297 s of the 4.1177 s window, most of it the 1.3294 s
    gap in round 8's batch synthesis, which waits on the writer's
    ``tobytes`` copies (``vc.writer.serialize``)."""
    raw = json.loads((DATA / "trace_local8_save.json").read_text())
    tr = PT.ProgramTrace.from_json(raw)
    ctx = ctx_of(tr)
    [writer] = tr.named("vc.writer")
    assert writer.args == {"step": 8, "bytes": 692888192}
    [to_host] = tr.named("vc.ckpt.to_host")
    assert to_host.args["bytes"] == 4 * 173221888
    assert [s.args["round"] for s in tr.named("vc.round")] == [7, 8]
    lo, hi = tr.window()
    assert (lo, hi) == (0.0, pytest.approx(4.1176683, rel=1e-6))
    assert ckpt_write_ms.read(ctx) == pytest.approx(3043.4328, rel=1e-6)
    # the idle gaps between ops, and the trailing idle to the window's end,
    # each clipped to the writer's span
    busy = tr.busy(0)
    gaps = trace.Trace.idle_gaps(tr, 0) + [(busy[-1][1], hi)]
    by_hand = sum(max(0.0, min(b, writer.end) - max(a, writer.start))
                  for a, b in gaps)
    assert by_hand == pytest.approx(1.1297158, rel=1e-6)
    assert ckpt_idle_ms.read(ctx) == pytest.approx(1e3 * by_hand / 2,
                                                  rel=1e-9)
    assert window_compiles.read(ctx) == 0.0
    [top] = tr.top_gaps(1)
    assert top[0] == "bench.round_batches + vc.writer.serialize"
    assert top[1] == pytest.approx(1.3293706, rel=1e-6)
