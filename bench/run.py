#!/usr/bin/env python3
"""Benchmark of the VC-ASGD round on the TPU, one cell per run.

    python3 bench/run.py --workload internlm2-1.8b.local8 --seed 7 \\
        --seconds 10 --trace 0

A cell is an entry of ``BENCHMARK.json``'s ``workloads``: a configuration
(``bench/configs/<config>.json``) under a traffic mix
(``bench/workloads/<traffic>.json``), with the limits of its check in
``bench/limits/<cell>.json``.  The configuration's ``"model"`` names its
reference and the program's side of it, ``bench/models/<model>.py`` and
``bench/models/<model>_program.py`` (see ``bench/models/__init__.py``).
A run:

1. set-up: makes the weights from ``--seed`` on the device in one jitted
   call, writes them as the server's checkpoint at the traffic's first
   round, and calls the program's trainer (``repro.launch.train.train``),
   which resumes from it; the trainer's first round (tracing, compiling or
   loading the compiled round, and running it) ends the set-up;
2. the window: the trainer's remaining rounds, a fixed number drawn from
   ``--seconds``, up to its return, which includes the last checkpoint's
   write.  A round's wall time runs from one round-end (the trainer's log
   line, after the loss is read back) to the next;
3. the check: the reference follows the first rounds, and ``bench/check.py``
   compares it with the losses the trainer returned and the checkpoints it
   wrote.

With ``--trace 1`` the window is profiled and the per-layer metrics are
read from the trace by ``bench/metrics/<name>.py``.  The last line of
standard output is the result as JSON.  A run without a TPU, or with fewer
chips than the cell asks for, exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from types import ModuleType  # noqa: E402
from typing import Dict, List  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import models  # noqa: E402
from bench import traffic as TR  # noqa: E402

CACHE_DIR = BENCH / ".jax_cache"
WORK_DIR = BENCH / ".work"


class NoChip(RuntimeError):
    pass


@dataclass
class Cell:
    name: str
    chips: int
    conf: dict
    model: ModuleType       # the reference, bench/models/<model>.py
    program: ModuleType     # the program's side, <model>_program.py
    traffic: TR.Traffic
    limits: Dict[str, float]
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` and everything it names, found by name."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    conf = json.loads((root / cfg["file"]).read_text())
    model, program = models.load(conf)
    traffic = TR.load(root / "bench" / "workloads" / f"{w['traffic']}.json")
    limits = json.loads((root / "bench" / "limits" / f"{name}.json")
                        .read_text())["limits"]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(name, w["chips"], conf, model, program, traffic, limits,
                e2e, per_layer)


def percentile(values: List[float], q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def use_devices(chips: int, require_tpu: bool = True):
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX sees {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{len(devices)}")
    return devices


def enable_compile_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def ckpt_name(step: int) -> str:
    return f"ckpt_{step:08d}.msgpack"


class Taps:
    """Taps on the trainer's calls into its layers, which offer no hook:
    its module attributes are wrapped for the run and restored after.
    Every run keeps, for the check, the batches of the first ``keep``
    rounds as the trainer produced them, and a hard link in ``kept`` to
    each checkpoint in ``follow`` that the trainer writes to ``ckpt_dir``
    (its files are written whole and renamed into place), made round each
    save, so that the trainer's own retention may delete the file.  A
    traced run also opens host spans round batch synthesis and the
    checkpoint save, which the profiler writes into its trace."""

    def __init__(self, module, keep: int, trace: bool, *, ckpt_dir: Path,
                 follow: List[int], kept: Path):
        self.m, self.keep, self.trace = module, keep, trace
        self.ckpt_dir, self.follow, self.kept = ckpt_dir, follow, kept
        self.saved = (module.round_batches, module.CheckpointManager)
        self.batches: List[dict] = []
        kept.mkdir(parents=True, exist_ok=True)

    def link_followed(self) -> None:
        for step in self.follow:
            dst = self.kept / ckpt_name(step)
            if not dst.exists():
                try:
                    os.link(self.ckpt_dir / ckpt_name(step), dst)
                except FileNotFoundError:
                    pass

    def __enter__(self):
        from jax.profiler import TraceAnnotation
        rb, mgr = self.saved
        taps = self

        def round_batches(*a, **k):
            if taps.trace:
                with TraceAnnotation("bench.round_batches"):
                    out = rb(*a, **k)
            else:
                out = rb(*a, **k)
            if len(taps.batches) < taps.keep:
                taps.batches.append(out)
            return out

        class Manager(mgr):
            def save(self, *a, **k):
                taps.link_followed()
                if taps.trace:
                    with TraceAnnotation("bench.ckpt_save"):
                        super().save(*a, **k)
                else:
                    super().save(*a, **k)
                taps.link_followed()

        self.m.round_batches = round_batches
        self.m.CheckpointManager = Manager
        return self

    def __exit__(self, *exc):
        self.m.round_batches, self.m.CheckpointManager = self.saved


def restore_step(ckpt_dir: Path, step: int, like, scratch: Path):
    """The server as the program resumes it from checkpoint ``step`` in
    ``ckpt_dir``: that file alone, in a directory of its own, through the
    trainer's own restore (``CheckpointManager.restore_or_init``)."""
    from repro.checkpoint import CheckpointManager
    name = ckpt_name(step)
    view = scratch / f"view_{step}"
    shutil.rmtree(view, ignore_errors=True)
    view.mkdir(parents=True)
    os.link(ckpt_dir / name, view / name)
    try:
        tree, _, got = CheckpointManager(view).restore_or_init(
            like, lambda: None)
    finally:
        shutil.rmtree(view, ignore_errors=True)
    if got != step:
        raise RuntimeError(f"restored step {got}, wanted {step}")
    return tree


def checkpoint_steps(ckpt_dir: Path) -> List[int]:
    return sorted(int(p.stem.split("_")[1])
                  for p in ckpt_dir.glob("ckpt_*.msgpack"))


def program_lr(spec):
    """The trainer's ``lr`` for a traffic's: the rate, or the program's
    own warm-up-and-cosine schedule, as its users build it."""
    if not isinstance(spec, dict):
        return float(spec)
    from repro.optim import cosine_schedule
    return cosine_schedule(spec["peak"], warmup=spec["warmup_steps"],
                           total=spec["total_steps"],
                           min_frac=spec["min_frac"])


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             devices, t_start: float, work: Path) -> dict:
    """Set-up, window and check of one run; returns the result line."""
    import jax
    import numpy as np
    from bench import check as C
    from bench.vc_ref import seed_words
    from repro.launch import train as T
    from repro.models.registry import build_model

    t = cell.traffic
    M = cell.model
    dm = M.dims(cell.conf)
    pcfg = cell.program.program_config(cell.conf)
    n_window = t.window_rounds(seconds)
    rounds = t.first_round + 1 + n_window
    # checkpoints the check reads: those of the rounds the reference follows
    follow = [s for s in range(t.first_round + 1,
                               t.first_round + t.check_rounds + 1)
              if s % t.ckpt_every == 0]
    shutil.rmtree(work, ignore_errors=True)
    ckpt_dir = work / "ckpt"
    kept = work / "kept"
    trace_dir = work / "trace"
    ckpt_dir.mkdir(parents=True)

    # -- set-up: the seeded weights become the server's checkpoint --------
    init = M.make_init(dm)
    words = seed_words(seed)
    weights = init(words)
    like = jax.eval_shape(lambda: build_model(pcfg).init(
        jax.random.PRNGKey(0)))
    if jax.tree.structure(like) != jax.tree.structure(weights) or any(
            a.shape != b.shape for a, b in zip(jax.tree.leaves(like),
                                               jax.tree.leaves(weights))):
        raise RuntimeError("the program's parameters are not laid out as "
                           "the reference's")
    mgr = T.CheckpointManager(ckpt_dir)
    mgr.save(t.first_round, weights, {"round": t.first_round})
    mgr.wait()
    del weights, mgr

    ends: List[float] = []

    def log(msg: str) -> None:
        if " loss=" not in msg:
            return
        ends.append(time.perf_counter())
        if trace and len(ends) == 1:
            jax.profiler.start_trace(str(trace_dir))
            ends[0] = time.perf_counter()

    taps = Taps(T, t.check_rounds, trace, ckpt_dir=ckpt_dir, follow=follow,
                kept=kept).__enter__()
    try:
        out = T.train(pcfg, ckpt_dir=str(ckpt_dir), islands=t.islands,
                      rounds=rounds, local_steps=t.local_steps,
                      batch=t.batch, seq=t.seq, alpha="var",
                      lr=program_lr(t.lr), mesh_shape=(1, 1),
                      preempt_round=(t.first_round if t.preempt_first_round
                                     else -1),
                      seed=seed, ckpt_every=t.ckpt_every, log=log)
        t_end = time.perf_counter()
        taps.link_followed()
    finally:
        taps.__exit__()
        if trace and ends:
            jax.profiler.stop_trace()
    used = devices[:max(cell.chips, t.islands)]
    mem_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in used)

    # -- end-to-end metrics ------------------------------------------------
    setup_s = ends[0] - t_start
    window_s = t_end - ends[0]
    round_s = [b - a for a, b in zip(ends, ends[1:])]
    losses = [float(x) for x in out["losses"]]
    window_losses = losses[1:]
    failed = sum(1 for x in window_losses if not math.isfinite(x))
    tokens = n_window * t.tokens_per_round
    e2e = {"tokens_per_s": tokens / window_s,
           "round_s_p90": percentile(round_s, 90) if round_s else
           float("nan"),
           "setup_s": setup_s}

    # -- the check -----------------------------------------------------------
    prog = C.Readout(losses=losses[:t.check_rounds], norms={},
                     tokens_bad=C.tokens_bad(t, dm.vocab, seed,
                                             taps.batches))
    final_host = jax.device_get(out["server"])
    del out, taps       # the trainer's state is freed before the reference
    steps = checkpoint_steps(ckpt_dir)
    last = t.first_round + 1 + n_window
    if steps and steps[-1] == last:
        restored = jax.device_get(restore_step(ckpt_dir, last, like, work))
        ckpt_bits = sum(
            1 for a, b in zip(jax.tree.leaves(final_host),
                              jax.tree.leaves(restored))
            if np.asarray(a).tobytes() != np.asarray(b).tobytes())
        del restored
    else:
        ckpt_bits = len(jax.tree.leaves(final_host))
    del final_host

    weights0 = init(words)
    for step in follow:
        if (kept / ckpt_name(step)).exists():
            s_k = restore_step(kept, step, like, work)
            prog.norms[step] = C.leaf_norms(s_k, weights0)
            del s_k
    ref = C.follow(M, dm, t, seed, weights0, devices=used)
    numbers = C.readings(prog, ref, ckpt_bits)
    limits = dict(cell.limits)
    if len(ends) != 1 + n_window:        # round-ends the log did not show
        numbers["rounds_off"] = abs(len(ends) - 1 - n_window)
        limits["rounds_off"] = 0
    correct = C.verdict(numbers, limits)

    dev = devices[0]
    result = {"correct": bool(correct and failed == 0),
              "attempted": n_window, "failed": failed}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    if trace:
        from bench import trace as TRC
        tr = TRC.load(trace_dir)
        ctx = Context(cell=cell, model=M, dm=dm, trace=tr,
                      window_s=window_s, rounds=n_window, tokens=tokens,
                      chips=cell.chips, device_kind=dev.device_kind)
        metrics = {}
        for m in cell.per_layer:
            v = importlib.import_module(f"bench.metrics.{m['name']}"
                                        ).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        busy = tr.mean_busy_s()
        device.update(busy_s=busy if busy is not None else 0.0,
                      window_s=window_s)
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {"device_ops": tr.top_ops(),
                               "idle_gaps": tr.top_gaps()}
    else:
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = device
    # a reading that could not be taken (no checkpoint, a loss that is not
    # finite) prints as null: it fails its limit all the same
    result["checks"] = {k: {"value": v if math.isfinite(v) else None,
                            "limit": limits[k]}
                        for k, v in numbers.items()}
    result["round_s"] = round_s
    return result


@dataclass
class Context:
    """What a per-layer metric reader is given."""
    cell: Cell
    model: ModuleType       # the cell's reference, for counts from sizes
    dm: object              # its sizes, model.dims(cell.conf)
    trace: object
    window_s: float
    rounds: int
    tokens: int
    chips: int
    device_kind: str


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        print("bench: --seed must be non-negative", file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    cell = load_cell(args.workload)
    try:
        devices = use_devices(cell.chips)
    except NoChip as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 2
    enable_compile_cache()
    sys.path.insert(0, str(ROOT / "src"))
    work = WORK_DIR / cell.name
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          devices=devices, t_start=T_START, work=work)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    round_s = result.pop("round_s")
    print(f"bench: {cell.name} seed {args.seed}: {len(round_s)} rounds "
          f"{[round(x, 4) for x in round_s]}", file=sys.stderr)
    for k, v in result["checks"].items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
