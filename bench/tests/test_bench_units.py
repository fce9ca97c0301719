"""The benchmark's yardstick on the CPU: discovery by name (cells, and
each configuration's model), configuration cuts, FLOP and byte counts, the
peak table, the trace reduction on a recorded trace, and the refusal to run
without a TPU."""
import json
import os
import subprocess
import sys
from pathlib import Path
from types import ModuleType

import pytest

from bench import models, peaks, run, trace, traffic, vc_ref, work

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]

# widths of the published config.json of each source (hand-copied)
PUBLISHED = {
    "internlm2-1.8b": {"hidden_size": 2048, "intermediate_size": 8192,
                       "num_attention_heads": 16, "num_key_value_heads": 8,
                       "rope_theta": 1000000, "rms_norm_eps": 1e-05,
                       "num_hidden_layers": 24, "vocab_size": 92544},
    "stablelm-3b": {"hidden_size": 2560, "intermediate_size": 6912,
                    "num_attention_heads": 32, "num_key_value_heads": 32,
                    "rope_theta": 10000, "layer_norm_eps": 1e-05,
                    "partial_rotary_factor": 0.25,
                    "num_hidden_layers": 32, "vocab_size": 50304},
}


def conf(name):
    c = {c["name"]: c for c in BENCH["configs"]}[name]
    return json.loads((ROOT / c["file"]).read_text())


def model(name):
    """The reference module the configuration ``name`` names."""
    return models.load(conf(name))[0]


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_config_keeps_published_widths_and_cuts_only_depth_and_vocab(name):
    c, pub = conf(name), PUBLISHED[name]
    entry = {c["name"]: c for c in BENCH["configs"]}[name]
    assert entry["reduced"] == c["reduced"] == ["num_hidden_layers",
                                                "vocab_size"]
    for k, v in pub.items():
        if k in c["reduced"]:
            assert c["published"][k] == v and c[k] < v
        else:
            assert c[k] == v, k
    assert c["vocab_size"] * 8 == pub["vocab_size"]


@pytest.mark.parametrize("name,want", [
    # 6 x matmul params + 6 x layers x heads x hd x seq (causal attention)
    ("internlm2-1.8b", 6 * (2 * (2048 * 128 * 48 + 3 * 2048 * 8192)
                            + 2048 * 11568) + 6 * 2 * 16 * 128 * 1024),
    ("stablelm-3b", 6 * (2 * (2560 * 80 * 128 + 3 * 2560 * 6912)
                         + 2560 * 6288) + 6 * 2 * 32 * 80 * 1024),
])
def test_flops_per_token_matches_hand_count(name, want):
    M = model(name)
    assert M.flops_per_token(M.dims(conf(name)), 1024) == want
    assert want in (922288128, 1079623680)


@pytest.mark.parametrize("name,n", [("internlm2-1.8b", 173221888),
                                    ("stablelm-3b", 190817280)])
def test_parameter_count_and_server_bytes(name, n):
    M = model(name)
    assert M.params(M.dims(conf(name))) == n
    assert work.server_bytes_per_chip(n, 1, 1) == 16 * n
    assert work.server_bytes_per_chip(n, 4, 4) == 10 * n


def test_peaks_known_and_unknown_device():
    assert peaks.lookup("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks.lookup("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.lookup("cpu")


@pytest.mark.parametrize("cell", CELLS)
def test_cells_are_found_by_name(cell):
    c = run.load_cell(cell, ROOT)
    w = {w["name"]: w for w in BENCH["workloads"]}[cell]
    assert c.chips == w["chips"] and c.traffic.name == w["traffic"]
    assert set(c.limits) == {"loss_gap", "update_gap", "ckpt_bits",
                             "tokens_bad"}
    assert {m["name"] for m in c.end_to_end} == {"tokens_per_s",
                                                 "round_s_p90", "setup_s"}
    names = {m["name"] for m in c.per_layer}
    assert ("collective_ms" in names) == (cell.endswith(".pod4"))
    for m in c.per_layer:
        mod = __import__(f"bench.metrics.{m['name']}",
                         fromlist=["read"])
        assert callable(mod.read)
    t = c.traffic
    assert t.window_rounds(10) % t.ckpt_every == 0
    assert (t.first_round + 1) % t.ckpt_every == 0


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        run.load_cell("no-such-cell", ROOT)


@pytest.mark.parametrize("name,registered,error,names", [
    ("no_such_lm", None, FileNotFoundError, "bench/models/no_such_lm.py"),
    ("half_lm", "full", FileNotFoundError,
     "bench/models/half_lm_program.py"),
    ("thin_lm", "without params", AttributeError, "['params']"),
])
def test_a_configuration_whose_model_has_no_module_is_refused(
        tmp_path, monkeypatch, name, registered, error, names):
    """``load_cell`` refuses, before any device is touched, a cell whose
    configuration names a model with no module under ``bench/models``
    (or one that lacks part of the interface), naming what is missing."""
    if registered:
        ref = ModuleType(f"bench.models.{name}")
        for attr in models.REFERENCE:
            setattr(ref, attr, None)
        if registered == "without params":
            del ref.params
        monkeypatch.setitem(sys.modules, ref.__name__, ref)
    raw = json.loads((DATA / "tiny.json").read_text())
    (tmp_path / "tiny.json").write_text(json.dumps({**raw, "model": name}))
    bench = {"configs": [{"name": "tiny", "file": "tiny.json"}],
             "workloads": [{"name": "tiny.local8", "config": "tiny",
                            "traffic": "local8", "chips": 1}],
             "end_to_end": [], "per_layer": []}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(error) as e:
        run.load_cell("tiny.local8", tmp_path)
    assert names in str(e.value)


def test_window_rounds_scale_with_seconds():
    t = traffic.load(ROOT / "bench" / "workloads" / "local8.json")
    assert t.window_rounds(20) == 8
    assert t.window_rounds(40) == 16
    assert t.window_rounds(0.1) == 4
    t = traffic.load(ROOT / "bench" / "workloads" / "sync1.json")
    assert t.window_rounds(20) == 10 and t.ckpt_every == 2


@pytest.mark.parametrize("spec", [
    1e-3, {"peak": 3e-4, "warmup_steps": 20, "total_steps": 10000,
           "min_frac": 0.1},
    {"peak": 1e-3, "warmup_steps": 4, "total_steps": 12, "min_frac": 0.1}])
def test_reference_rate_follows_the_trainers_schedule(spec):
    import jax.numpy as jnp
    import numpy as np
    ref = vc_ref.lr_schedule(spec)
    prog = run.program_lr(spec)
    for t in (1, 2, 3, 4, 5, 8, 19, 20, 21, 100, 9999, 10000, 20000):
        step = jnp.asarray(t, jnp.int32)
        want = prog(step) if callable(prog) else prog
        np.testing.assert_allclose(float(ref(step)), float(want),
                                   rtol=1e-6)


def test_a_schedule_needs_every_key(tmp_path):
    raw = json.loads((DATA / "tiny_warmup.json").read_text())
    del raw["lr"]["min_frac"]
    (tmp_path / "bad.json").write_text(json.dumps(raw))
    with pytest.raises(ValueError, match="schedule"):
        traffic.load(tmp_path / "bad.json")


def test_followed_checkpoints_outlive_the_trainers_retention(tmp_path):
    """The trainer keeps its newest checkpoints only; the taps keep a
    hard link to each one the check reads."""
    from types import SimpleNamespace
    import numpy as np
    from repro.checkpoint import CheckpointManager
    mod = SimpleNamespace(round_batches=None,
                          CheckpointManager=CheckpointManager)
    ckpt = tmp_path / "ckpt"
    taps = run.Taps(mod, 0, False, ckpt_dir=ckpt, follow=[2, 3],
                    kept=tmp_path / "kept").__enter__()
    try:
        mgr = mod.CheckpointManager(ckpt, keep=2)
        for step in range(1, 7):
            mgr.save(step, {"w": np.full(3, step, np.float32)})
        mgr.wait()
        taps.link_followed()
    finally:
        taps.__exit__()
    assert mod.CheckpointManager is CheckpointManager
    assert run.checkpoint_steps(ckpt) == [5, 6]
    for step in (2, 3):
        tree = run.restore_step(tmp_path / "kept", step,
                                {"w": np.zeros(3, np.float32)}, tmp_path)
        assert float(tree["w"][0]) == step


def test_tokens_are_seeded_and_large_seeds_work():
    t = traffic.load(DATA / "tiny_traffic.json")
    a = traffic.tokens(t, 256, 2 ** 31 + 5, 1, 0, 0)
    b = traffic.tokens(t, 256, 2 ** 31 + 5, 1, 0, 0)
    c = traffic.tokens(t, 256, 2 ** 31 + 5, 1, 0, 1)
    assert a.shape == (t.batch, t.seq) and (a == b).all()
    assert not (a == c).all() and a.max() < 256
    assert list(vc_ref.seed_words(2 ** 33 + 7)) == [7, 2]


def test_percentile_is_linear_between_ranks():
    assert run.percentile([1.0, 2.0, 3.0, 4.0], 90) == pytest.approx(3.7)
    assert run.percentile([5.0], 90) == 5.0


def test_trace_reduction_on_synthetic_ops():
    tr = trace.Trace(
        ops={0: [trace.Op("%while.3 = (f32[8]) while(f32[8] %p)", 0.0, 1.5),
                 trace.Op("%fusion.1 = f32[8,8] fusion(f32[8] %a)", 0.0, 1.0),
                 trace.Op("%fusion.2 = f32[8] fusion(f32[8] %b)", 0.5, 1.0),
                 trace.Op("%fusion.3 = f32[1,4096] fusion(f32[4096] %c)",
                          3.0, 0.5),
                 trace.Op("%all-to-all.1 = f32[4,1024] all-to-all("
                          "f32[4,1024] %d)", 4.0, 0.25)]},
        spans=[trace.Span("bench.ckpt_save", 1.6, 1.2)])
    assert tr.busy(0) == [(0.0, 1.5), (3.0, 3.5), (4.0, 4.25)]
    assert tr.busy_s(0) == pytest.approx(2.25)
    assert tr.idle_gaps(0) == [(1.5, 3.0), (3.5, 4.0)]
    assert tr.top_gaps(1) == [["bench.ckpt_save", 1.5]]
    assert tr.top_gaps(2)[1][0] == "host: trainer loop"
    assert tr.op_seconds(lambda o: o.largest_dim >= 1024) == {0: 0.75}
    assert tr.op_seconds(lambda o: o.collective) == {0: 0.25}
    assert [n for n, _ in tr.top_ops(2)] == ["fusion.1", "fusion.2"]
    back = trace.Trace.from_json(json.loads(json.dumps(tr.to_json())))
    assert back.busy(0) == tr.busy(0) and back.spans == tr.spans


def test_refuses_without_a_tpu():
    with pytest.raises(run.NoChip):
        run.use_devices(1)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_reduction_of_a_recorded_chip_trace():
    """One round of internlm2-1.8b.local8 traced on a TPU v5e (device
    plane ``/device:TPU:0``, line ``XLA Ops``; ops of 0.2 ms or more,
    loops and every op on the flat bus kept).  Hand counts on the full
    trace: the round took 1.40676 s host to host, the scan over the 8
    local steps (``while.236``) 1.34170 s, and the 71 ops on the bus
    22.303 ms."""
    from types import SimpleNamespace
    from bench.metrics import (batch_ms, device_idle, server_dev_ms,
                               server_roofline)
    raw = json.loads((DATA / "trace_local8_round.json").read_text())
    tr = trace.Trace.from_json(raw)
    loop = [o for o in tr.ops[0] if o.name == "while.236"]
    assert len(loop) == 1 and loop[0].dur == pytest.approx(1.3416964)
    assert tr.busy_s(0) == pytest.approx(1.3765680, rel=1e-6)
    assert tr.top_ops(1)[0][0] == "bitcast_dynamic-update-slice_fusion.23"
    M = model("internlm2-1.8b")
    ctx = SimpleNamespace(trace=tr, model=M,
                          dm=M.dims(conf("internlm2-1.8b")),
                          chips=1, rounds=1, window_s=1.4067599,
                          device_kind="TPU v5 lite",
                          cell=SimpleNamespace(traffic=SimpleNamespace(
                              islands=1, seq=1024)))
    assert server_dev_ms.read(ctx) == pytest.approx(22.302988, rel=1e-6)
    least = 16 * 173221888 / 819e9
    assert server_roofline.read(ctx) == pytest.approx(
        100 * least / 0.022302988, rel=1e-6)
    assert device_idle.read(ctx) == pytest.approx(
        100 * (1 - 1.3765680 / 1.4067599), rel=1e-5)
    assert batch_ms.read(ctx) == pytest.approx(22.268244, rel=1e-6)


def test_refuses_in_a_checkout_without_the_program(tmp_path):
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".work", ".jax_cache",
                                                  "__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1"],
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0 and p.stdout == ""
