"""Checkpoint durability: bit-exact round trip (incl. bf16), retention,
kill/restore resume semantics, the one-pass (params + m + v) train
record — including a real-SIGKILL atomicity test (slow) — and the file
format's bytes, written with no payload-sized copy."""
import os
import signal
import subprocess
import sys
import time
import tracemalloc
from pathlib import Path

import jax
import jax.numpy as jnp
import msgpack
import numpy as np
import pytest

from repro.checkpoint import (CheckpointManager, load_checkpoint,
                              load_flat_checkpoint, load_train_checkpoint,
                              save_checkpoint, save_flat_checkpoint,
                              save_train_checkpoint)
from repro.core import flat as F
from repro.optim import Adam


def _tree(key):
    ks = jax.random.split(key, 3)
    return {"w": jax.random.normal(ks[0], (33, 17), jnp.float32),
            "b": (jax.random.normal(ks[1], (9,), jnp.bfloat16),
                  jnp.arange(5, dtype=jnp.int32)),
            "n": jax.random.normal(ks[2], (2, 3, 4))}


def test_roundtrip_bit_exact(tmp_path):
    t = _tree(jax.random.PRNGKey(0))
    save_checkpoint(tmp_path / "c.msgpack", t, {"step": 7})
    out, extra = load_checkpoint(tmp_path / "c.msgpack", t)
    assert extra["step"] == 7
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(out)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_manager_retention_and_resume(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2, async_save=False)
    t = _tree(jax.random.PRNGKey(1))
    for step in (1, 2, 3, 4):
        t2 = jax.tree.map(lambda x: x + step if x.dtype != jnp.int32 else x, t)
        mgr.save(step, t2, {"round": step})
    assert mgr.latest_step() == 4
    ckpts = sorted((tmp_path).glob("ckpt_*.msgpack"))
    assert len(ckpts) == 2                            # retention

    # simulated restart: fresh manager restores the newest snapshot
    mgr2 = CheckpointManager(tmp_path, keep=2)
    restored, extra, step = mgr2.restore_or_init(t, lambda: t)
    assert step == 4 and extra["round"] == 4
    np.testing.assert_allclose(np.asarray(restored["w"]),
                               np.asarray(t["w"]) + 4)


def test_restore_or_init_fresh(tmp_path):
    mgr = CheckpointManager(tmp_path / "empty")
    t = _tree(jax.random.PRNGKey(2))
    out, extra, step = mgr.restore_or_init(t, lambda: t)
    assert step == 0 and extra == {}
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(t["w"]))


def test_async_save_completes(tmp_path):
    mgr = CheckpointManager(tmp_path, async_save=True)
    t = _tree(jax.random.PRNGKey(3))
    mgr.save(1, t)
    mgr.wait()
    assert mgr.latest_step() == 1


# ---------------------------------------------------------------------------
# one-pass train checkpoints: params + m + v as three lanes of ONE record
# ---------------------------------------------------------------------------

def _train_state(key, n_steps=3):
    tree = {"w": jax.random.normal(key, (40, 9)),
            "b": jax.random.normal(jax.random.fold_in(key, 1), (17,))}
    opt = Adam(lr=1e-2)
    fp = F.flatten(tree)
    fos = opt.init_flat(fp)
    for i in range(n_steps):
        g = F.flatten_like(jax.tree.map(
            lambda x: jax.random.normal(jax.random.fold_in(key, 10 + i),
                                        x.shape), tree), fp.spec)
        fp, fos = opt.update_flat(g, fos, fp)
    return fp, fos


def test_train_checkpoint_roundtrip(tmp_path):
    fp, fos = _train_state(jax.random.PRNGKey(0))
    save_train_checkpoint(tmp_path / "t.msgpack", fp, fos, {"round": 9})
    fp2, fos2, extra = load_train_checkpoint(tmp_path / "t.msgpack", fp)
    assert extra["round"] == 9
    assert int(fos2.step) == int(fos.step) == 3
    np.testing.assert_array_equal(np.asarray(fp.buf), np.asarray(fp2.buf))
    np.testing.assert_array_equal(np.asarray(fos.m), np.asarray(fos2.m))
    np.testing.assert_array_equal(np.asarray(fos.v), np.asarray(fos2.v))


def test_train_checkpoint_is_one_contiguous_record(tmp_path):
    """The whole (params, m, v) state is ONE msgpack binary record — a
    header plus exactly one buffer write, no per-leaf packing."""
    fp, fos = _train_state(jax.random.PRNGKey(1))
    save_train_checkpoint(tmp_path / "t.msgpack", fp, fos)
    with open(tmp_path / "t.msgpack", "rb") as f:
        objs = list(msgpack.Unpacker(f, raw=False, max_buffer_size=2 ** 31))
    assert len(objs) == 2                  # header + ONE record
    header, record = objs
    assert header["kind"] == "flat-train"
    assert len(record) == sum(header["lane_bytes"])
    assert len(record) == 3 * fp.spec.padded * 4      # three f32 lanes


def test_train_checkpoint_kind_mismatch_raises(tmp_path):
    fp, fos = _train_state(jax.random.PRNGKey(2))
    save_train_checkpoint(tmp_path / "train.msgpack", fp, fos)
    save_flat_checkpoint(tmp_path / "flat.msgpack", fp)
    with pytest.raises(ValueError):
        load_flat_checkpoint(tmp_path / "train.msgpack", fp)
    with pytest.raises(ValueError):
        load_train_checkpoint(tmp_path / "flat.msgpack", fp)


def test_train_checkpoint_layout_mismatch_raises(tmp_path):
    fp, fos = _train_state(jax.random.PRNGKey(3))
    save_train_checkpoint(tmp_path / "t.msgpack", fp, fos)
    other = F.flatten({"z": jnp.zeros((5,))})
    with pytest.raises(ValueError):
        load_train_checkpoint(tmp_path / "t.msgpack", other)


def test_manager_train_resume(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2, async_save=False)
    fp, fos = _train_state(jax.random.PRNGKey(4))
    mgr.save_train(5, fp, fos, {"round": 5})
    mgr2 = CheckpointManager(tmp_path)
    (fp2, fos2), extra, step = mgr2.restore_train_or_init(
        fp, lambda: (None, None))
    assert step == 5 and extra["round"] == 5
    np.testing.assert_array_equal(np.asarray(fp.buf), np.asarray(fp2.buf))
    np.testing.assert_array_equal(np.asarray(fos.v), np.asarray(fos2.v))


# ---------------------------------------------------------------------------
# the file format: payloads framed by hand must give the bytes msgpack
# gives (the loaders, and every checkpoint already on disk, rely on it),
# in every bin size class, with no payload-sized copy on the writer.
# ---------------------------------------------------------------------------

def _raw(arr):
    arr = np.asarray(arr)
    if arr.dtype == jnp.bfloat16:
        return "bfloat16", arr.view(np.uint16).tobytes()
    return str(arr.dtype), arr.tobytes()


def _packed(*objs) -> bytes:
    return b"".join(msgpack.packb(o, use_bin_type=True) for o in objs)


def _oracle_bytes(kind, state, extra) -> bytes:
    """The file each saver must write, framed the plain way: ``tobytes``
    copies, each object through ``msgpack.packb``."""
    if kind == "tree":
        leaves, treedef = jax.tree.flatten(state)
        raws = [_raw(leaf) for leaf in leaves]
        header = {"treedef": str(treedef),
                  "metas": [{"dtype": d, "shape": np.shape(leaf)}
                            for (d, _), leaf in zip(raws, leaves)],
                  "extra": extra}
        return _packed(header, *(r for _, r in raws))
    fp = state if kind == "flat" else state[0]
    dtype, p = _raw(fp.buf)
    if kind == "flat":
        return _packed({"flat": fp.spec.meta(), "buf_dtype": dtype,
                        "treedef": str(fp.spec.treedef), "extra": extra}, p)
    opt = state[1]
    m = np.asarray(opt.m, np.float32).tobytes()
    v = np.asarray(opt.v, np.float32).tobytes()
    return _packed({"kind": "flat-train", "flat": fp.spec.meta(),
                    "buf_dtype": dtype, "lane_bytes": [len(p), len(m), len(v)],
                    "step": int(opt.step), "treedef": str(fp.spec.treedef),
                    "extra": extra}, p + m + v)


def _format_state(kind, n, dtype):
    """A tree, a FlatParams of ``n`` elements of ``dtype`` (unpadded), or
    such a FlatParams with Adam lanes, for ``kind``'s saver."""
    ks = jax.random.split(jax.random.PRNGKey(n), 4)
    if kind == "tree":           # payloads of every bin size class's edges
        return {"f32": jax.random.normal(ks[0], (3, 5), jnp.float32),
                "bf16": jax.random.normal(ks[1], (7,), jnp.bfloat16),
                "i32": jnp.arange(6, dtype=jnp.int32).reshape(2, 3),
                "scalar": (jnp.float32(2.5), jnp.bfloat16(-1.5),
                           jnp.int32(-7)),
                "empty": (jnp.zeros((0,), jnp.float32),
                          jnp.zeros((0,), jnp.bfloat16)),
                "edges": [jax.random.randint(ks[2], (b,), 0, 255, jnp.int32
                                             ).astype(jnp.uint8)
                          for b in (255, 256, 65535)]
                + [jnp.arange(65536 // 4, dtype=jnp.int32)]}
    fp = F.flatten({"w": jax.random.normal(ks[0], (n,))}, dtype=dtype,
                   pad_to=1)
    if kind == "flat":
        return fp
    return fp, F.FlatOptState(
        m=jax.random.normal(ks[1], (n,), jnp.float32),
        v=jax.random.uniform(ks[2], (n,), jnp.float32),
        step=jnp.asarray(11, jnp.int32), spec=fp.spec)


_SAVERS = {"tree": lambda path, st, e: save_checkpoint(path, st, e),
           "flat": lambda path, st, e: save_flat_checkpoint(path, st, e),
           "train": lambda path, st, e: save_train_checkpoint(path, *st, e)}

# (kind, elements, dtype): payload bytes on both sides of each bin size
# class's edge (bin 8 up to 255 bytes, bin 16 up to 65,535, then bin 32);
# a train record is 2 + 4 + 4 = 10 bytes an element with bf16 params,
# 12 with float32 params.
_FORMAT_CASES = [
    ("tree", 0, None),
    ("flat", 63, jnp.float32), ("flat", 128, jnp.bfloat16),
    ("flat", 16383, jnp.float32), ("flat", 16384, jnp.float32),
    ("train", 25, jnp.bfloat16), ("train", 26, jnp.bfloat16),
    ("train", 6553, jnp.bfloat16), ("train", 5462, jnp.float32),
]


@pytest.mark.parametrize(
    "kind,n,dtype", _FORMAT_CASES,
    ids=[f"{k}-{n}-{jnp.dtype(d).name if d else 'leaves'}"
         for k, n, d in _FORMAT_CASES])
def test_saved_file_bytes_match_msgpack_framing(tmp_path, kind, n, dtype):
    state = _format_state(kind, n, dtype)
    extra = {"round": 3, "tag": "x"}
    path = tmp_path / "c.msgpack"
    _SAVERS[kind](path, state, extra)
    assert path.read_bytes() == _oracle_bytes(kind, state, extra)
    assert not list(tmp_path.glob("*.tmp"))

    if kind == "tree":
        out, got_extra = load_checkpoint(path, state)
        want, have = jax.tree.leaves(state), jax.tree.leaves(out)
    elif kind == "flat":
        out, got_extra = load_flat_checkpoint(path, state)
        want, have = [state.buf], [out.buf]
    else:
        fp, opt, got_extra = load_train_checkpoint(path, state[0])
        assert int(opt.step) == 11
        want = [state[0].buf, state[1].m, state[1].v]
        have = [fp.buf, opt.m, opt.v]
    assert got_extra == extra
    for a, b in zip(want, have):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def _host(x):
    return jax.tree.map(np.asarray, x)


@pytest.mark.parametrize("kind", ["tree", "train"])
def test_save_makes_no_payload_sized_copy(tmp_path, kind):
    """The writer saves the host arrays ``CheckpointManager._to_host``
    hands it; tracing allocations (numpy's included), its peak over the
    save stays under 1/16 of the ~64 MB payload."""
    rng = np.random.default_rng(0)
    if kind == "tree":
        state = {"w": rng.standard_normal((2048, 4096), np.float32),
                 "e": rng.standard_normal((1024, 4096), np.float32),
                 "b": rng.standard_normal((2048, 4096), np.float32
                                          ).astype(jnp.bfloat16)}
        payload = sum(a.nbytes for a in state.values())
    else:
        fp = F.flatten({"w": rng.standard_normal((2048, 2731), np.float32)})
        state = _host((fp, F.FlatOptState(m=fp.buf, v=fp.buf,
                                          step=jnp.asarray(1, jnp.int32),
                                          spec=fp.spec)))
        payload = 3 * state[0].buf.nbytes
    assert payload >= 60 << 20
    tracemalloc.start()
    try:
        _SAVERS[kind](tmp_path / "c.msgpack", state, None)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert peak < payload // 16, (peak, payload)
    assert (tmp_path / "c.msgpack").stat().st_size > payload


def test_payload_of_4gib_raises_like_msgpack_and_leaves_no_file(tmp_path):
    """A payload msgpack cannot frame (2**32 bytes or more) raises
    msgpack's ValueError before a byte of it is copied, and the temp file
    goes."""
    huge = np.lib.stride_tricks.as_strided(np.zeros(1, np.uint8),
                                           shape=(1 << 32,), strides=(0,))
    with pytest.raises(ValueError, match="too large"):
        save_checkpoint(tmp_path / "c.msgpack", {"huge": huge})
    assert list(tmp_path.iterdir()) == []


# ---------------------------------------------------------------------------
# REAL kill: SIGKILL the training process mid-run, then restore.  Atomic
# rename means the newest committed record always loads cleanly, and the
# resumed trajectory equals the uninterrupted one at matching steps.
# ---------------------------------------------------------------------------

_CHILD = """
import sys, time
sys.path.insert(0, sys.argv[2])
from repro.core.simulator import run_preemptible_training
from repro.core.tasks import MLPTask, make_classification_data

task = MLPTask()
data = make_classification_data(n_train=600, n_val=100)
print("READY", flush=True)
run_preemptible_training(task, data, steps=10 ** 9, batch=32, ckpt_every=3,
                         ckpt_dir=sys.argv[1], seed=5,
                         on_step=lambda s: time.sleep(0.01))
"""


@pytest.mark.slow
def test_sigkill_mid_training_restores_and_matches(tmp_path):
    from repro.core.simulator import run_preemptible_training
    from repro.core.tasks import MLPTask, make_classification_data

    src = str(Path(__file__).resolve().parents[1] / "src")
    ckpt_dir = tmp_path / "ckpt"
    proc = subprocess.Popen([sys.executable, "-c", _CHILD, str(ckpt_dir), src],
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE)
    try:
        assert proc.stdout.readline().strip() == b"READY"
        # let it train + checkpoint for a while, then pull the plug
        deadline = time.time() + 60
        while time.time() < deadline:
            time.sleep(0.5)
            ckpts = list(ckpt_dir.glob("ckpt_*.msgpack"))
            if len(ckpts) >= 3:
                break
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)

    committed = list(ckpt_dir.glob("ckpt_*.msgpack"))
    assert committed, ("child process wrote no checkpoint before the kill "
                       "(machine too slow? raise the deadline)")

    task = MLPTask()
    data = make_classification_data(n_train=600, n_val=100)
    key = jax.random.PRNGKey(5)
    like = F.flatten(task.init_params(key))
    # the newest COMMITTED record loads cleanly (atomic rename: no torn file)
    mgr = CheckpointManager(ckpt_dir)
    (fp, fos), extra, step = mgr.restore_train_or_init(like, lambda: None)
    assert step > 0 and step % 3 == 0 and extra["step"] == step
    assert int(fos.step) == step

    # resuming from the survivor reproduces the uninterrupted trajectory
    horizon = step + 6
    resumed = run_preemptible_training(task, data, steps=horizon, batch=32,
                                       ckpt_every=3, ckpt_dir=ckpt_dir,
                                       seed=5)
    clean = run_preemptible_training(task, data, steps=horizon, batch=32,
                                     ckpt_every=3,
                                     ckpt_dir=tmp_path / "clean", seed=5)
    for s in range(step, horizon):
        assert resumed.losses[s] == clean.losses[s], s
    np.testing.assert_array_equal(np.asarray(resumed.final_params.buf),
                                  np.asarray(clean.final_params.buf))
