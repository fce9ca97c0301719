"""A whole run of the benchmark on the CPU at a tiny size, through the
program's trainer: a sound run is correct and prints the result line's
schema; the control (the reference in float8 in the program's place) and
each fault planted under the timed path make ``correct`` false.

The harness's look for a chip is skipped (``run_cell`` is called with
the CPU's devices); everything after it runs as on the chip."""
import json
import os
import subprocess
import sys
import time
from collections import Counter
from pathlib import Path
from types import ModuleType

import jax
import pytest

from bench import check as C
from bench import control as K
from bench import models, peaks, run, traffic

DATA = Path(__file__).resolve().parent / "data"
ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def tiny_cell(traffic_file="tiny_traffic.json", model=None):
    conf = json.loads((DATA / "tiny.json").read_text())
    if model:
        conf["model"] = model
    limits = json.loads((DATA / "tiny_limits.json").read_text())["limits"]
    return run.Cell("tiny", 1, conf, *models.load(conf),
                    traffic.load(DATA / traffic_file), limits,
                    BENCH["end_to_end"],
                    [m for m in BENCH["per_layer"] if "workloads" not in m])


def run_tiny(tmp_path, seed=2 ** 31 + 11, trace=False,
             traffic_file="tiny_traffic.json"):
    return run.run_cell(tiny_cell(traffic_file), seed, 10, trace,
                        devices=jax.devices(), t_start=time.perf_counter(),
                        work=tmp_path / "work")


# a constant rate, and a warm-up and cosine schedule that runs past its
# warm-up inside the followed rounds (8 Adam steps, warm-up 4)
@pytest.mark.parametrize("traffic_file", ["tiny_traffic.json",
                                          "tiny_warmup.json"])
def test_sound_run_is_correct_with_the_result_schema(tmp_path,
                                                     traffic_file):
    r = run_tiny(tmp_path, traffic_file=traffic_file)
    assert r["correct"] is True
    assert list(r)[-2:] == ["checks", "round_s"]
    assert r["attempted"] == 3 and r["failed"] == 0
    assert set(r["metrics"]) == {"tokens_per_s", "round_s_p90", "setup_s"}
    for m in r["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert set(r["device"]) == {"platform", "kind", "count",
                                "memory_peak_bytes"}
    assert set(r["checks"]) == {"loss_gap", "update_gap", "ckpt_bits",
                                "tokens_bad"}
    assert all("value" in v and "limit" in v for v in r["checks"].values())
    assert len(r["round_s"]) == 3


def test_traced_run_reports_per_layer_metrics_it_can_read(tmp_path):
    cell = tiny_cell()
    # the CPU is in no peak table: leave out the shares of a peak
    cell.per_layer = [m for m in cell.per_layer
                      if m["name"] not in ("mfu", "server_roofline")]
    r = run.run_cell(cell, 5, 10, True, devices=jax.devices(),
                     t_start=time.perf_counter(), work=tmp_path / "work")
    assert r["correct"] is True
    assert set(r["device"]) >= {"busy_s", "window_s"}
    # no TPU plane on the CPU: only the host spans' readers find something
    assert set(r["metrics"]) == {"ckpt_stall_ms", "batch_ms"}
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def test_a_model_added_as_new_modules_drives_the_whole_run(tmp_path,
                                                           monkeypatch):
    """A model module under a new name, registered as the files
    ``bench/models/counted_lm.py`` and ``counted_lm_program.py`` would be,
    drives a traced run to ``correct``: the harness calls that module's
    interface, and nothing of ``dense_lm`` directly."""
    from bench.models import dense_lm, dense_lm_program
    calls = Counter()

    def counted(fn):
        def call(*a, **k):
            calls[fn.__name__] += 1
            return fn(*a, **k)
        return call

    def refused(*a, **k):
        raise AssertionError("dense_lm called directly")

    ref = ModuleType("bench.models.counted_lm")
    prog = ModuleType("bench.models.counted_lm_program")
    for name in models.REFERENCE:
        real = getattr(dense_lm, name)
        setattr(ref, name, counted(real) if callable(real) else real)
        if callable(real):
            monkeypatch.setattr(dense_lm, name, refused)
    prog.program_config = counted(dense_lm_program.program_config)
    monkeypatch.setattr(dense_lm_program, "program_config", refused)
    monkeypatch.setitem(sys.modules, ref.__name__, ref)
    monkeypatch.setitem(sys.modules, prog.__name__, prog)
    # the CPU is in no peak table: read the chip's, so that mfu asks the
    # model for its FLOPs (the shares read are not the CPU's, and unused)
    chip = peaks.lookup("TPU v5 lite")
    monkeypatch.setattr(peaks, "lookup", lambda kind: chip)

    cell = tiny_cell(model="counted_lm")
    assert (cell.model, cell.program) == (ref, prog)
    r = run.run_cell(cell, 2 ** 31 + 17, 10, True, devices=jax.devices(),
                     t_start=time.perf_counter(), work=tmp_path / "work")
    assert r["correct"] is True, r["checks"]
    assert set(calls) == {"dims", "make_init", "nll_sum", "flops_per_token",
                          "params", "program_config"}, calls


def _break_round(monkeypatch, how):
    from repro.launch import train as T
    real = T.make_vc_round

    def broken(*a, **k):
        vc_round = real(*a, **k)

        def round_(server, islands, opts, batches, alpha, survivors):
            if how == "half_batch":
                batches = jax.tree.map(lambda x: x[:, :, : x.shape[2] // 2],
                                       batches)
            out = vc_round(server, islands, opts, batches, alpha, survivors)
            if how == "unchanged":
                return (server, islands, opts, out[3])
            return out
        return round_
    monkeypatch.setattr(T, "make_vc_round", broken)


def _alter_tokens(monkeypatch):
    from repro.launch import train as T
    real = T.round_batches

    def altered(cfg, *a, **k):
        b = real(cfg, *a, **k)
        t = b["tokens"]
        mid = t.shape[-1] // 2
        b["tokens"] = t.at[..., mid].set((t[..., mid] + 1) % cfg.vocab_size)
        return b
    monkeypatch.setattr(T, "round_batches", altered)


def _other_rate(monkeypatch):
    """The trainer run at a rate other than the traffic's."""
    real = run.program_lr
    monkeypatch.setattr(run, "program_lr",
                        lambda spec: real({**spec, "peak": 2 * spec["peak"]}))


@pytest.mark.parametrize("fault", ["unchanged", "half_batch",
                                   "alter_token", "other_rate"])
def test_fault_under_the_timed_path_fails_the_check(tmp_path, monkeypatch,
                                                    fault):
    if fault == "alter_token":
        _alter_tokens(monkeypatch)
    elif fault == "other_rate":
        _other_rate(monkeypatch)
    else:
        _break_round(monkeypatch, fault)
    r = run_tiny(tmp_path, traffic_file="tiny_warmup.json"
                 if fault == "other_rate" else "tiny_traffic.json")
    assert r["correct"] is False
    over = [k for k, v in r["checks"].items() if v["value"] > v["limit"]]
    assert over, r["checks"]
    if fault == "alter_token":
        # 4 rows x 2 steps x 3 rounds followed, one token each
        assert r["checks"]["tokens_bad"]["value"] == 24


def test_control_in_the_programs_place_fails_the_check():
    cell = tiny_cell()
    lines = []
    K.readings_for(cell, 2 ** 31 + 12, ["fp8"], jax.devices(),
                   out=lines.append)
    numbers = json.loads(lines[0])["readings"]
    assert not C.verdict(numbers, cell.limits), numbers


POD = r'''
import json, sys, time
from pathlib import Path
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import jax
from bench import run, traffic
from bench.tests.test_bench_check import tiny_cell
fault = sys.argv[3]
if fault == "no_exchange":
    import jax.numpy as jnp
    from repro.runtime import vc_runtime as V
    def local_only(server_buf, islands_buf, w, w_s, **_):
        n, N = islands_buf.shape
        owner = jnp.arange(N) * n // N
        keep = owner[None, :] == jnp.arange(n)[:, None]
        contrib = jnp.sum(jnp.where(keep, w[:, None] * islands_buf, 0.0), 0)
        return (w_s + w.sum() - jnp.where(keep, w[:, None], 0).sum(0)
                ) * server_buf + contrib
    V.assimilate_flat = local_only
cell = tiny_cell("tiny_pod.json")
cell.chips = 4
r = run.run_cell(cell, 2 ** 31 + 13, 10, False, devices=jax.devices(),
                 t_start=time.perf_counter(), work=Path(sys.argv[2]))
print(json.dumps({"correct": r["correct"], "checks": r["checks"]}))
'''


@pytest.mark.parametrize("fault", ["none", "no_exchange"])
def test_four_islands_on_four_devices_and_the_exchange_left_out(tmp_path,
                                                                fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", POD, str(ROOT),
                        str(tmp_path / "w"), fault], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.splitlines()[-1])
    assert r["correct"] is (fault == "none"), r
