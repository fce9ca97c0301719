"""Checkpointing: msgpack tensor store with atomic rename, async save,
retention, and restart logic.

This is the durability layer of the VC design: the *server copy* is the
only state that must survive (clients/islands are disposable by design —
the paper's whole point), so checkpoints are snapshots of
(server params, opt state, round counter, alpha-schedule position, data
cursor).  ``CheckpointManager.restore_or_init`` is what every launcher
calls first: a preempted coordinator resumes exactly where the last
assimilation left off.

Server state on the FlatParams bus (core/flat.py) takes the flat path:
``save_flat_checkpoint`` writes the TreeSpec offset table in the header
and the parameter set as ONE contiguous buffer (no leaf-by-leaf packing);
the manager routes FlatParams there automatically.

File format, shared by every saver: a msgpack header object, then each
payload as one msgpack bin object (bfloat16 as its uint16 bits).  The
payloads are framed by hand (``_write_bin``: the bin header msgpack would
write, then the array's own buffer) and written through an unbuffered
file, so no Python-level copy of a payload is made and each ``write``
syscall runs without the interpreter lock, which the trainer's main thread
needs meanwhile.  The bytes are those ``msgpack.packb(
arr.tobytes(), use_bin_type=True)`` gives, so the loaders read them with
a plain ``msgpack.Unpacker``.
"""
from __future__ import annotations

import json
import os
import shutil
import tempfile
import threading
from pathlib import Path
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import msgpack
import numpy as np

from repro import obs


def _write_all(f, buf) -> None:
    """Write every byte of ``buf`` to the unbuffered file ``f``, looping
    over partial writes."""
    view = memoryview(buf).cast("B")
    while view:
        view = view[f.write(view):]


def _write_bin(f, *arrays: np.ndarray) -> None:
    """Write ``arrays``' bytes back to back as ONE msgpack bin object, the
    bytes ``msgpack.packb(b"".join(a.tobytes() for a in arrays),
    use_bin_type=True)`` gives, straight from the arrays' buffers (no copy
    for C-contiguous arrays)."""
    n = sum(a.nbytes for a in arrays)
    if n < 1 << 8:
        _write_all(f, b"\xc4" + n.to_bytes(1, "big"))
    elif n < 1 << 16:
        _write_all(f, b"\xc5" + n.to_bytes(2, "big"))
    elif n < 1 << 32:
        _write_all(f, b"\xc6" + n.to_bytes(4, "big"))
    else:
        raise ValueError("bytes object is too large")
    for a in arrays:
        _write_all(f, np.ascontiguousarray(a).reshape(-1).view(np.uint8))


def _write_file(path: Path, header: Dict, records, *,
                step: Optional[int]) -> None:
    """Atomic write, in the span ``vc.writer.write``: ``header``, then each
    record (a tuple of arrays, framed as ONE bin object) to a temp file in
    ``path``'s directory, then the rename over ``path``."""
    with obs.span("vc.writer.write", step=step):
        fd, tmp = tempfile.mkstemp(dir=path.parent, suffix=".tmp")
        try:
            with os.fdopen(fd, "wb", buffering=0) as f:
                _write_all(f, msgpack.packb(header, use_bin_type=True))
                for arrays in records:
                    _write_bin(f, *arrays)
            os.replace(tmp, path)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)


def _tree_to_payload(tree) -> Tuple[Dict, list]:
    """The header (dtype and shape per leaf) and the host arrays whose
    bytes are stored, one per leaf."""
    leaves, treedef = jax.tree.flatten(tree)
    metas, arrays = [], []
    for leaf in leaves:
        arr = np.asarray(jax.device_get(leaf))
        dtype, raw = _buf_to_raw(arr)
        metas.append({"dtype": dtype, "shape": arr.shape})
        arrays.append(raw)
    return {"treedef": str(treedef), "metas": metas}, arrays


def save_checkpoint(path: str | Path, tree, extra: Optional[Dict] = None,
                    *, step: Optional[int] = None) -> None:
    """Atomic save: write to a temp file in the same dir, then rename.
    ``step`` tags the writer's spans (``repro.obs``)."""
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    with obs.span("vc.writer.serialize", step=step):
        header, arrays = _tree_to_payload(tree)
    header["extra"] = extra or {}
    _write_file(path, header, [(arr,) for arr in arrays], step=step)


def load_checkpoint(path: str | Path, tree_like) -> Tuple[Any, Dict]:
    """Restore into the structure of `tree_like` (shapes must match)."""
    path = Path(path)
    leaves, treedef = jax.tree.flatten(tree_like)
    with open(path, "rb") as f:
        unpacker = msgpack.Unpacker(f, raw=False, max_buffer_size=2 ** 31)
        header = next(unpacker)
        out = []
        for meta, like in zip(header["metas"], leaves):
            buf = next(unpacker)
            if meta["dtype"] == "bfloat16":
                arr = np.frombuffer(buf, np.uint16).reshape(meta["shape"])
                arr = jnp.asarray(arr.view(jnp.bfloat16))
            else:
                arr = jnp.asarray(np.frombuffer(
                    buf, np.dtype(meta["dtype"])).reshape(meta["shape"]))
            out.append(arr)
    return jax.tree.unflatten(treedef, out), header.get("extra", {})


# ---------------------------------------------------------------------------
# flat-bus checkpoints (core/flat.py): ONE contiguous buffer write instead
# of leaf-by-leaf packing.  The TreeSpec offset table rides in the header;
# the treedef itself (not serializable) is re-derived from `tree_like` at
# load, exactly like load_checkpoint.
# ---------------------------------------------------------------------------

def save_flat_checkpoint(path: str | Path, fp, extra: Optional[Dict] = None,
                         *, step: Optional[int] = None) -> None:
    """Atomic save of a FlatParams: header (layout + extra) + one buffer.
    ``step`` tags the writer's spans (``repro.obs``)."""
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    with obs.span("vc.writer.serialize", step=step):
        buf_dtype, raw = _buf_to_raw(np.asarray(jax.device_get(fp.buf)))
    header = {"flat": fp.spec.meta(), "buf_dtype": buf_dtype,
              "treedef": str(fp.spec.treedef), "extra": extra or {}}
    _write_file(path, header, [(raw,)], step=step)


def load_flat_checkpoint(path: str | Path, like) -> Tuple[Any, Dict]:
    """Restore a FlatParams saved by save_flat_checkpoint.

    ``like`` supplies the treedef: a FlatParams, a TreeSpec, or a template
    tree with the same structure.  The stored offset table is validated
    against it (shape/offset mismatch -> ValueError, not silent garbage)."""
    from repro.core import flat as F
    path = Path(path)
    spec = _spec_of(like)
    with open(path, "rb") as f:
        unpacker = msgpack.Unpacker(f, raw=False, max_buffer_size=2 ** 31)
        header = next(unpacker)
        raw = next(unpacker)
    if header.get("kind") == "flat-train":
        raise ValueError(f"{path} is a train checkpoint (params+m+v); "
                         f"use load_train_checkpoint")
    _check_layout(header["flat"], spec, path)
    buf = _buf_from_bytes(raw, header["buf_dtype"])
    return F.FlatParams(buf, spec), header.get("extra", {})


def _spec_of(like):
    from repro.core import flat as F
    if isinstance(like, F.FlatParams):
        return like.spec
    if isinstance(like, F.TreeSpec):
        return like
    return F.tree_spec(like)


def _check_layout(meta: Dict, spec, path) -> None:
    # sharded layouts (ShardedTreeSpec) pin the segment geometry: a record
    # written n_shards-way only restores onto the same partitioning.
    # Checked FIRST so the error names the shard mismatch (the padded
    # length usually differs too, which the generic check would mask).
    from repro.core import flat as F
    want = None
    if isinstance(spec, F.ShardedTreeSpec):
        want = {"n_shards": spec.n_shards, "shard_len": spec.shard_len,
                "axis": spec.axis}
    have = meta.get("shard")
    if want != have:
        raise ValueError(
            f"flat checkpoint shard-layout mismatch: record {have} vs "
            f"requested {want}: {path}")
    if (tuple(tuple(s) for s in meta["shapes"]) != spec.shapes
            or tuple(meta["offsets"]) != spec.offsets
            or meta["n"] != spec.n or meta["padded"] != spec.padded):
        raise ValueError(f"flat checkpoint layout mismatch: {path}")


def _buf_from_bytes(raw: bytes, dtype_name: str) -> jnp.ndarray:
    if dtype_name == "bfloat16":
        return jnp.asarray(np.frombuffer(raw, np.uint16).view(jnp.bfloat16))
    return jnp.asarray(np.frombuffer(raw, np.dtype(dtype_name)))


def _buf_to_raw(arr: np.ndarray) -> Tuple[str, np.ndarray]:
    """Encode twin of _buf_from_bytes: the dtype's name and the array whose
    bytes are stored (bf16 rides as its uint16 bits, a view, not a copy)."""
    if arr.dtype == jnp.bfloat16:
        return "bfloat16", arr.view(np.uint16)
    return str(arr.dtype), arr


# ---------------------------------------------------------------------------
# one-pass TRAIN checkpoints: params + Adam m/v as THREE LANES OF ONE
# CONTIGUOUS RECORD.  The whole training state (params, m, v, step) is
# written as one bin object, lane after lane, and restored atomically —
# the resume path after preemption
# (core/simulator.py::run_preemptible_training) is one read, zero leaf
# walks.
# ---------------------------------------------------------------------------

def save_train_checkpoint(path: str | Path, fp, opt,
                          extra: Optional[Dict] = None, *,
                          step: Optional[int] = None) -> None:
    """Atomic save of (FlatParams, FlatOptState): one header + ONE
    contiguous record laid out as [params | m | v].  ``step`` tags the
    writer's spans (``repro.obs``)."""
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    if fp.spec.padded != opt.spec.padded or fp.spec.shapes != opt.spec.shapes:
        raise ValueError("params and optimizer state do not share a layout")
    with obs.span("vc.writer.serialize", step=step):
        p_dtype, p_raw = _buf_to_raw(np.asarray(jax.device_get(fp.buf)))
        m_raw = np.asarray(jax.device_get(opt.m), np.float32)
        v_raw = np.asarray(jax.device_get(opt.v), np.float32)
        header = {"kind": "flat-train", "flat": fp.spec.meta(),
                  "buf_dtype": p_dtype,
                  "lane_bytes": [p_raw.nbytes, m_raw.nbytes, v_raw.nbytes],
                  "step": int(jax.device_get(opt.step)),
                  "treedef": str(fp.spec.treedef), "extra": extra or {}}
    _write_file(path, header, [(p_raw, m_raw, v_raw)], step=step)


def load_train_checkpoint(path: str | Path, like) -> Tuple[Any, Any, Dict]:
    """Restore (FlatParams, FlatOptState, extra) saved by
    save_train_checkpoint.  ``like`` supplies the layout exactly as in
    load_flat_checkpoint; the record is sliced into its three lanes by the
    header's byte offsets — no per-leaf unpacking."""
    from repro.core import flat as F
    path = Path(path)
    spec = _spec_of(like)
    with open(path, "rb") as f:
        unpacker = msgpack.Unpacker(f, raw=False, max_buffer_size=2 ** 31)
        header = next(unpacker)
        record = next(unpacker)
    if header.get("kind") != "flat-train":
        raise ValueError(f"{path} is not a train checkpoint; "
                         f"use load_flat_checkpoint")
    _check_layout(header["flat"], spec, path)
    lp, lm, lv = header["lane_bytes"]
    if len(record) != lp + lm + lv:
        raise ValueError(f"torn train checkpoint record: {path}")
    buf = _buf_from_bytes(record[:lp], header["buf_dtype"])
    m = jnp.asarray(np.frombuffer(record[lp:lp + lm], np.float32))
    v = jnp.asarray(np.frombuffer(record[lp + lm:], np.float32))
    opt = F.FlatOptState(m=m, v=v,
                         step=jnp.asarray(header["step"], jnp.int32),
                         spec=spec)
    return F.FlatParams(buf, spec), opt, header.get("extra", {})


class CheckpointManager:
    """Rolling checkpoints with async save and retention.

    save() snapshots on the calling thread's values but writes on a
    background thread (double-buffered — training never blocks on disk),
    mirroring how a real cluster writes to replicated object storage.
    """

    def __init__(self, directory: str | Path, keep: int = 3,
                 async_save: bool = True):
        self.dir = Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.keep = keep
        self.async_save = async_save
        self._pending: Optional[threading.Thread] = None

    def _path(self, step: int) -> Path:
        return self.dir / f"ckpt_{step:08d}.msgpack"

    def save(self, step: int, tree, extra: Optional[Dict] = None) -> None:
        with obs.span("vc.ckpt.save", step=step):
            with obs.span("vc.ckpt.join", step=step):
                self.wait()
            from repro.core import flat as F
            flat = isinstance(tree, F.FlatParams)
            host_tree = self._to_host(step, tree)

            def work():
                with obs.span("vc.writer", step=step) as span:
                    if flat:
                        save_flat_checkpoint(self._path(step), host_tree,
                                             extra, step=step)
                    else:
                        save_checkpoint(self._path(step), host_tree, extra,
                                        step=step)
                    span.set_metadata(bytes=self._path(step).stat().st_size)
                    with obs.span("vc.writer.retain", step=step):
                        self._gc()

            self._start(work)

    def save_train(self, step: int, fp, opt,
                   extra: Optional[Dict] = None) -> None:
        """One-pass (params + m + v) snapshot; same retention/async rules
        as save()."""
        with obs.span("vc.ckpt.save", step=step):
            with obs.span("vc.ckpt.join", step=step):
                self.wait()
            host_fp, host_opt = self._to_host(step, (fp, opt))

            def work():
                with obs.span("vc.writer", step=step) as span:
                    save_train_checkpoint(self._path(step), host_fp,
                                          host_opt, extra, step=step)
                    span.set_metadata(bytes=self._path(step).stat().st_size)
                    with obs.span("vc.writer.retain", step=step):
                        self._gc()

            self._start(work)

    def _start(self, work) -> None:
        """Run ``work`` on the writer thread, or here without async."""
        if self.async_save:
            self._pending = threading.Thread(target=work, daemon=True)
            self._pending.start()
        else:
            work()

    @staticmethod
    def _to_host(step: int, tree):
        """``tree`` copied to host arrays, in the span ``vc.ckpt.to_host``
        with the bytes copied."""
        with obs.span("vc.ckpt.to_host", step=step) as span:
            host = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), tree)
            span.set_metadata(bytes=sum(a.nbytes for a in
                                        jax.tree.leaves(host)))
        return host

    def restore_train_or_init(self, like, init_fn):
        """Resume (params, opt state) from the newest train checkpoint or
        initialize fresh.  Returns ((fp, opt), extra, step)."""
        self.wait()
        step = self.latest_step()
        if step is None:
            return init_fn(), {}, 0
        fp, opt, extra = load_train_checkpoint(self._path(step), like)
        return (fp, opt), extra, step

    def wait(self) -> None:
        if self._pending is not None:
            self._pending.join()
            self._pending = None

    def _gc(self) -> None:
        ckpts = sorted(self.dir.glob("ckpt_*.msgpack"))
        for old in ckpts[:-self.keep]:
            old.unlink(missing_ok=True)

    def latest_step(self) -> Optional[int]:
        ckpts = sorted(self.dir.glob("ckpt_*.msgpack"))
        if not ckpts:
            return None
        return int(ckpts[-1].stem.split("_")[1])

    # -- protocol server checkpoints (protocol/coordinator.py hooks) --------
    # the durable VC state is (server params, version): params ride the
    # one-pass flat path, the version counter rides the header.  Leases
    # and residuals are deliberately not persisted — in-flight work is
    # disposable by design and a restarted coordinator reissues it.

    def save_server(self, step: int, fp, version: int,
                    extra: Optional[Dict] = None) -> None:
        e = dict(extra or {})
        e["server_version"] = int(version)
        self.save(step, fp, e)

    def restore_server_or_init(self, like, init_fn):
        """Resume (params, version) from the newest checkpoint or init
        fresh.  Returns (params, version, extra, step) — ``extra`` is the
        caller-supplied dict save_server persisted alongside, so runtimes
        can resume their own counters (uids, round offsets)."""
        tree, extra, step = self.restore_or_init(like, init_fn)
        return tree, int(extra.get("server_version", 0)), extra, step

    def restore_or_init(self, tree_like, init_fn):
        """Resume from the newest checkpoint or initialize fresh.
        Returns (tree, extra, step)."""
        self.wait()
        from repro.core import flat as F
        step = self.latest_step()
        if step is None:
            return init_fn(), {}, 0
        if isinstance(tree_like, (F.FlatParams, F.TreeSpec)):
            tree, extra = load_flat_checkpoint(self._path(step), tree_like)
        else:
            tree, extra = load_checkpoint(self._path(step), tree_like)
        return tree, extra, step
