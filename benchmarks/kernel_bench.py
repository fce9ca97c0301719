"""Kernel microbenchmarks.

On this CPU container the Pallas kernels run in interpret mode (orders of
magnitude slower than compiled), so wall-time here benchmarks the *oracle*
(pure-jnp, XLA-compiled) path — the apples-to-apples number for the CSV —
and separately validates that the Pallas path agrees numerically.  On a TPU
the same harness times the Mosaic kernels.

Suites with a fused-launch story also emit a numeric ``_launches`` dict
(pallas_call counts per path) — ``benchmarks/run.py --check`` gates those
against the committed baseline (results/BASELINE_launches.json).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as K
from repro.kernels import ref as R


def _time(fn: Callable, *args, iters: int = 5) -> float:
    fn_j = jax.jit(fn)
    out = fn_j(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn_j(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6     # us


def bench_kernels() -> Dict[str, Dict]:
    rng = jax.random.PRNGKey(0)
    ks = jax.random.split(rng, 8)
    out = {}

    # vc-asgd lerp over a 16M-param tensor: HBM-pass throughput
    n = 1 << 24
    s = jax.random.normal(ks[0], (n,), jnp.float32)
    c = jax.random.normal(ks[1], (n,), jnp.float32)
    us = _time(lambda a, b: R.vc_asgd_lerp(a, b, 0.95), s, c)
    gbps = 3 * n * 4 / (us * 1e-6) / 1e9                # 2 reads + 1 write
    out["vc_asgd_lerp_16M"] = {"us_per_call": round(us, 1),
                               "derived": f"{gbps:.1f}GB/s"}

    q = jax.random.normal(ks[2], (1, 8, 1024, 64), jnp.float32) * 0.3
    k = jax.random.normal(ks[3], (1, 2, 1024, 64), jnp.float32) * 0.3
    v = jax.random.normal(ks[4], (1, 2, 1024, 64), jnp.float32)
    us = _time(lambda a, b, c_: R.attention(a, b, c_, causal=True), q, k, v)
    fl = 2 * 2 * 8 * 1024 * 1024 * 64 / 2               # causal half
    out["attention_1k"] = {"us_per_call": round(us, 1),
                           "derived": f"{fl / (us * 1e-6) / 1e9:.1f}GFLOP/s"}

    r_ = jax.random.normal(ks[5], (2, 4, 128, 64)) * 0.3
    w = jax.nn.sigmoid(jax.random.normal(ks[6], (2, 4, 128, 64))) * 0.5 + 0.4
    u = jax.random.normal(ks[7], (4, 64)) * 0.2
    us = _time(lambda a, b, c_, d, e: R.wkv6(a, b, c_, d, e),
               r_, r_, r_, w, u)
    out["wkv6_T128"] = {"us_per_call": round(us, 1), "derived": "-"}

    x = jax.random.normal(ks[0], (1 << 22,))
    us = _time(lambda a: R.quantize_int8(a)[0], x)
    out["quantize_int8_4M"] = {"us_per_call": round(us, 1),
                               "derived":
                               f"{x.size * 4 / (us * 1e-6) / 1e9:.1f}GB/s"}

    # numerical agreement of the Pallas path (small shapes, interpret mode)
    sp = jax.random.normal(ks[0], (4096,))
    cp = jax.random.normal(ks[1], (4096,))
    err = float(jnp.max(jnp.abs(K.fused_lerp(sp, cp, 0.9)
                                - R.vc_asgd_lerp(sp, cp, 0.9))))
    out["pallas_vs_ref_lerp"] = {"us_per_call": 0.0,
                                 "derived": f"maxerr={err:.1e}"}
    return out


def bench_flat_assimilate(*, n_clients: int = 4, write_json: bool = True
                          ) -> Dict[str, Dict]:
    """flat_vs_treemap: the FlatParams bus (core/flat.py) against the
    per-leaf tree walk it replaced.

    (a) Eq. 2 assimilation — n sequential per-leaf tree.map lerp folds vs
        ONE fused pass over the stacked [n_clients, N] flat buffer;
    (b) compressed assimilation — the per-leaf × per-island top-k loop
        (compressed_assimilate_per_leaf) vs ONE global top-k per island on
        the flat bus;
    (c) launch-count evidence that the fused Pallas path is a single
        ``pallas_call`` for the whole multi-leaf model.

    Writes results/BENCH_flat_assimilate.json so the perf trajectory of the
    flat path is recorded from this PR onward.
    """
    from repro.core import flat as F
    from repro.core import vc_asgd as V
    from repro.kernels import vc_asgd_update as VK
    from repro.runtime.vc_runtime import (compressed_assimilate,
                                          compressed_assimilate_per_leaf)

    key = jax.random.PRNGKey(0)
    # multi-leaf model, heterogeneous leaf sizes (~2.1M params over 24 leaves)
    sizes = [(256, 256), (1024, 64), (64,), (512, 512), (128, 1024), (1024,)]
    tree = {}
    for rep in range(4):
        for i, shp in enumerate(sizes):
            k2 = jax.random.fold_in(key, rep * 16 + i)
            tree[f"layer{rep}/p{i}"] = jax.random.normal(k2, shp, jnp.float32)
    n_leaves = len(jax.tree.leaves(tree))
    n_params = sum(x.size for x in jax.tree.leaves(tree))
    clients = [jax.tree.map(
        lambda x, c=c: x + 0.01 * jax.random.normal(
            jax.random.fold_in(key, 1000 + c), x.shape), tree)
        for c in range(n_clients)]
    alpha = 0.9

    fp = F.flatten(tree)
    cbuf = jnp.stack([F.flatten_like(c, fp.spec) for c in clients])

    # (a) Eq. 2: per-leaf folds vs one flat pass (both XLA-jitted; on this
    # CPU container the Pallas path runs interpret-mode, so the jnp flat
    # form is the apples-to-apples timing — see module docstring)
    def per_leaf(s, cs):
        folded = s
        for c in cs:
            folded = V.vc_asgd_update(folded, c, alpha)
        return folded

    us_tree = _time(per_leaf, tree, clients, iters=20)
    us_flat = _time(lambda s, cb: V.assimilate_many_flat(s, cb, alpha),
                    fp, cbuf, iters=20)

    # (c) launch counts through the Pallas entry points (trace-time)
    VK.reset_launch_count()
    V.assimilate_many_flat(fp, cbuf, alpha, use_kernel=True)
    launches_flat = VK.launch_count()
    VK.reset_launch_count()
    for c in clients:
        V.vc_asgd_update(tree, c, alpha, use_kernel=True)
    launches_per_leaf = VK.launch_count()

    # (b) compressed assimilation: per-leaf × per-island loop vs flat global
    # (both jitted + warmed via _time, like (a) — a cold eager call would
    # mostly measure tracing the 24x4 per-leaf top-k graphs)
    islands = jax.tree.map(lambda *xs: jnp.stack(xs), *clients)
    surv = jnp.ones((n_clients,), bool)

    us_comp_leaf = _time(
        lambda t, i: compressed_assimilate_per_leaf(t, i, alpha, surv,
                                                    density=0.05)[0],
        tree, islands, iters=3)
    us_comp_flat = _time(
        lambda t, i: compressed_assimilate(t, i, alpha, surv,
                                           density=0.05)[0],
        tree, islands, iters=3)

    out = {
        # no commas in derived: run.py prints name,us_per_call,derived CSV
        "model": {"us_per_call": 0.0,
                  "derived": f"{n_leaves} leaves / {int(n_params)} params / "
                             f"{n_clients} clients / padded={fp.spec.padded}"},
        "assimilate_treemap": {"us_per_call": round(us_tree, 1),
                               "derived": f"{n_leaves * n_clients} lerps"},
        "assimilate_flat": {"us_per_call": round(us_flat, 1),
                            "derived":
                            f"speedup={us_tree / max(us_flat, 1e-9):.2f}x"},
        "pallas_launches": {"us_per_call": 0.0,
                            "derived": f"flat={launches_flat} "
                                       f"per_leaf={launches_per_leaf}"},
        "compressed_per_leaf": {"us_per_call": round(us_comp_leaf, 1),
                                "derived":
                                f"{n_leaves}x{n_clients} topk calls"},
        "compressed_flat": {"us_per_call": round(us_comp_flat, 1),
                            "derived": f"speedup="
                            f"{us_comp_leaf / max(us_comp_flat, 1e-9):.2f}x"},
        "_launches": {"flat": launches_flat, "per_leaf": launches_per_leaf},
    }
    if write_json:
        results = Path(__file__).resolve().parents[1] / "results"
        results.mkdir(exist_ok=True)
        (results / "BENCH_flat_assimilate.json").write_text(
            json.dumps(out, indent=1))
    return out


def bench_flat_adam(*, write_json: bool = True) -> Dict[str, Dict]:
    """flat_vs_treemap for the OPTIMIZER: Adam with m/v as lanes of the
    FlatParams bus (Adam.update_flat) against the per-leaf tree.map path
    (Adam.update) it mirrors bit-for-bit.

    (a) wall-clock of one whole-model Adam step, both XLA-jitted (on this
        CPU container the Pallas path runs interpret-mode, so the jnp flat
        form is the apples-to-apples timing);
    (b) launch-count evidence that the fused Pallas path
        (kernels/vc_asgd_update.py::adam_update_flat) performs the whole
        multi-leaf update in a SINGLE ``pallas_call``;
    (c) one-pass checkpoint size/shape of the (params | m | v) record
        (checkpoint/store.py::save_train_checkpoint).

    Writes results/BENCH_flat_adam.json — the perf trajectory of the flat
    optimizer path is recorded from PR 2 onward.
    """
    import tempfile

    from repro.checkpoint import save_train_checkpoint
    from repro.core import flat as F
    from repro.kernels import vc_asgd_update as VK
    from repro.optim import Adam

    key = jax.random.PRNGKey(0)
    # same ~2.1M-param / 24-leaf model as bench_flat_assimilate
    sizes = [(256, 256), (1024, 64), (64,), (512, 512), (128, 1024), (1024,)]
    tree = {}
    for rep in range(4):
        for i, shp in enumerate(sizes):
            k2 = jax.random.fold_in(key, rep * 16 + i)
            tree[f"layer{rep}/p{i}"] = jax.random.normal(k2, shp, jnp.float32)
    n_leaves = len(jax.tree.leaves(tree))
    n_params = sum(x.size for x in jax.tree.leaves(tree))
    grads = jax.tree.map(
        lambda x: 0.01 * jax.random.normal(jax.random.fold_in(key, 999),
                                           x.shape), tree)

    opt = Adam(lr=1e-3, weight_decay=0.01)
    state_t = opt.init(tree)
    fp = F.flatten(tree)
    fos = opt.init_flat(fp)
    gbuf = F.flatten_like(grads, fp.spec)

    # (a) one Adam step: per-leaf tree walk vs one flat pass (both jitted)
    us_tree = _time(lambda g, s, p: opt.update(g, s, p)[0],
                    grads, state_t, tree, iters=20)
    us_flat = _time(lambda g, s, p: opt.update_flat(g, s, p)[0],
                    gbuf, fos, fp, iters=20)

    # (b) launch counts through the fused Pallas path (trace-time)
    VK.reset_launch_count()
    opt.update_flat(gbuf, fos, fp, use_kernel=True)
    launches_flat = VK.launch_count()

    # (c) the one-pass train record: (params | m | v) as one contiguous blob
    with tempfile.TemporaryDirectory() as d:
        path = Path(d) / "train.msgpack"
        t0 = time.perf_counter()
        save_train_checkpoint(path, fp, fos)
        us_ckpt = (time.perf_counter() - t0) * 1e6
        ckpt_bytes = path.stat().st_size

    out = {
        # no commas in derived: run.py prints name,us_per_call,derived CSV
        "model": {"us_per_call": 0.0,
                  "derived": f"{n_leaves} leaves / {int(n_params)} params / "
                             f"padded={fp.spec.padded}"},
        "adam_treemap": {"us_per_call": round(us_tree, 1),
                         "derived": f"{n_leaves} leaf walks x3 trees"},
        "adam_flat": {"us_per_call": round(us_flat, 1),
                      "derived":
                      f"speedup={us_tree / max(us_flat, 1e-9):.2f}x"},
        "pallas_launches": {"us_per_call": 0.0,
                            "derived": f"flat={launches_flat} "
                                       f"(vs {n_leaves} per-leaf)"},
        "train_ckpt_one_pass": {"us_per_call": round(us_ckpt, 1),
                                "derived": f"{ckpt_bytes} bytes single "
                                           f"record (params|m|v)"},
        "_launches": {"flat": launches_flat},
    }
    if write_json:
        results = Path(__file__).resolve().parents[1] / "results"
        results.mkdir(exist_ok=True)
        (results / "BENCH_flat_adam.json").write_text(
            json.dumps(out, indent=1))
    return out


# pre-PR wall-clock of the compressed_flat assimilation on this container
# (the committed results/BENCH_flat_assimilate.json before the blocked
# top-k landed) — the denominator of the compression suite's speedup row
_PRE_BLOCKED_TOPK_US = 801836.2


def bench_compression(*, write_json: bool = True) -> Dict[str, Dict]:
    """The compression hot path end to end on the bench-scale bus
    (~2.1M params, density 0.05):

    (a) blocked top-k selection (core/compression.py::select_topk) and the
        full compress_flat pass (select + quantize + error feedback);
    (b) the fused wire encode leg — encode_sparse packs the frame body in
        ONE device buffer / ONE host transfer — plus decode and the dense
        decompress;
    (c) launch counts of the blocked Pallas pipeline (stats + exact-k
        emit) and of the wire pack (XLA, none), gated by ``run.py
        --check`` like the other suites.

    Writes results/BENCH_compression.json.
    """
    from repro.core import compression as C
    from repro.kernels import vc_asgd_update as VK
    from repro.runtime.vc_runtime import compressed_assimilate
    from repro.transfer import wire

    key = jax.random.PRNGKey(0)
    n_logical = 2101504                  # bench-model logical params
    n_padded = 2105344                   # BLOCK=256-padded bus length
    density = 0.05
    k = max(1, int(n_logical * density))
    delta = 0.02 * jax.random.normal(key, (n_padded,), jnp.float32)
    residual = 0.002 * jax.random.normal(jax.random.fold_in(key, 1),
                                         (n_padded,), jnp.float32)

    us_select = _time(lambda d: C.select_topk(d, k), delta, iters=5)
    us_compress = _time(
        lambda d, r: C.compress_flat(d, density=density, logical_n=n_logical,
                                     residual=r)[1],
        delta, residual, iters=5)

    payload, _ = C.compress_flat(delta, density=density, logical_n=n_logical,
                                 residual=residual)
    jax.block_until_ready(payload.values)

    frame = wire.encode_sparse(payload)          # warm the jitted pack
    t0 = time.perf_counter()
    for _ in range(10):
        frame = wire.encode_sparse(payload)
    us_encode = (time.perf_counter() - t0) / 10 * 1e6
    t0 = time.perf_counter()
    for _ in range(10):
        wire.decode(frame)
    us_decode = (time.perf_counter() - t0) / 10 * 1e6

    us_decompress = _time(
        lambda v, s, i: C.decompress_flat(
            C.CompressedDelta(v, s, i, (n_padded,), density, 256)),
        payload.values, payload.scales, payload.indices, iters=5)

    # (c) launch counts of the Pallas pipeline (trace-time, interpret mode)
    small = 0.02 * jax.random.normal(jax.random.fold_in(key, 2),
                                     (C._MIN_FAST_N,), jnp.float32)
    VK.reset_launch_count()
    K.blocked_topk_sparsify(small, int(C._MIN_FAST_N * density))
    launches_topk = VK.launch_count()
    # the wire pack is XLA (kernels/ref.py::pack_body): no Pallas launch
    VK.reset_launch_count()
    wire.encode_sparse(payload)
    launches_pack = VK.launch_count()

    # end-to-end compressed assimilation on the SAME 24-leaf/4-island model
    # bench_flat_assimilate times — apples-to-apples with the committed
    # pre-PR wall-clock
    sizes = [(256, 256), (1024, 64), (64,), (512, 512), (128, 1024), (1024,)]
    tree = {}
    for rep in range(4):
        for i, shp in enumerate(sizes):
            k2 = jax.random.fold_in(key, rep * 16 + i)
            tree[f"layer{rep}/p{i}"] = jax.random.normal(k2, shp, jnp.float32)
    clients = [jax.tree.map(
        lambda x, c=c: x + 0.01 * jax.random.normal(
            jax.random.fold_in(key, 1000 + c), x.shape), tree)
        for c in range(4)]
    islands = jax.tree.map(lambda *xs: jnp.stack(xs), *clients)
    surv = jnp.ones((4,), bool)
    us_total = _time(
        lambda t, i: compressed_assimilate(t, i, 0.9, surv, density=0.05)[0],
        tree, islands, iters=3)

    out = {
        # no commas in derived: run.py prints name,us_per_call,derived CSV
        "model": {"us_per_call": 0.0,
                  "derived": f"n={n_logical} padded={n_padded} k={k} "
                             f"density={density}"},
        "select_topk": {"us_per_call": round(us_select, 1),
                        "derived": "blocked exact top-k (sampled bracket)"},
        "compress_flat": {"us_per_call": round(us_compress, 1),
                          "derived": "select+quantize+error-feedback"},
        "encode_sparse": {"us_per_call": round(us_encode, 1),
                          "derived": f"{len(frame)} bytes one-transfer body"},
        "decode": {"us_per_call": round(us_decode, 1),
                   "derived": "validate+split frame"},
        "decompress_flat": {"us_per_call": round(us_decompress, 1),
                            "derived": "dequant+scatter to dense"},
        "compressed_vs_pre_pr": {
            "us_per_call": round(us_total, 1),
            "derived": f"speedup={_PRE_BLOCKED_TOPK_US / max(us_total, 1e-9):.2f}x"
                       f" vs pre-blocked-topk {_PRE_BLOCKED_TOPK_US:.0f}us"},
        "pallas_launches": {"us_per_call": 0.0,
                            "derived": f"blocked_topk={launches_topk} "
                                       f"pack_body={launches_pack}"},
        "_launches": {"blocked_topk": launches_topk,
                      "pack_body": launches_pack},
    }
    if write_json:
        results = Path(__file__).resolve().parents[1] / "results"
        results.mkdir(exist_ok=True)
        (results / "BENCH_compression.json").write_text(
            json.dumps(out, indent=1))
    return out


def _bench_sharded_flat_impl(n_shards: int) -> Dict[str, Dict]:
    """Runs inside a process that has >= n_shards devices."""
    from repro.core import flat as F
    from repro.core import vc_asgd as V
    from repro.kernels import vc_asgd_update as VK
    from repro.launch.mesh import make_pod_mesh
    from repro.runtime import sharding as S

    key = jax.random.PRNGKey(0)
    # same ~2.1M-param / 24-leaf model as the other flat suites
    sizes = [(256, 256), (1024, 64), (64,), (512, 512), (128, 1024), (1024,)]
    tree = {}
    for rep in range(4):
        for i, shp in enumerate(sizes):
            k2 = jax.random.fold_in(key, rep * 16 + i)
            tree[f"layer{rep}/p{i}"] = jax.random.normal(k2, shp, jnp.float32)
    n_leaves = len(jax.tree.leaves(tree))
    n_clients = 4
    alpha = 0.9

    mesh = make_pod_mesh(n_shards)
    fp = F.flatten_sharded(tree, n_shards)
    clients = jnp.stack([fp.buf + 0.01 * (c + 1) for c in range(n_clients)])
    w = V.assimilation_weights(n_clients, alpha)

    # (a) flatten: single-host layout vs sharded layout (same leaf packing,
    # shard-aware tail) — both XLA-jitted
    us_flat_single = _time(lambda t: F.flatten(t).buf, tree, iters=20)
    us_flat_shard = _time(lambda t: F.flatten_sharded(t, n_shards).buf,
                          tree, iters=20)

    # (b) Eq. 2 assimilation: single-host fold vs per-shard shard_map
    us_assim_single = _time(
        lambda s, c: V.assimilate_many_flat(s, c, alpha), fp, clients,
        iters=20)
    us_assim_shard = _time(
        lambda sb, c: S.sharded_assimilate_flat(sb, c, w, mesh, "pod"),
        fp.buf, clients, iters=20)

    # (c) launch counts (trace-time): the sharded kernel route is STILL one
    # pallas_call for the whole model — shard_map partitions the one
    # launch, it does not multiply it
    VK.reset_launch_count()
    V.assimilate_many_flat(fp, clients, alpha, use_kernel=True)
    launches_single = VK.launch_count()
    VK.reset_launch_count()
    S.sharded_assimilate_flat(fp.buf, clients, w, mesh, "pod",
                              use_kernel=True)
    launches_shard = VK.launch_count()
    VK.reset_launch_count()
    per_leaf_clients = [F.unflatten(fp.with_buf(clients[c]))
                        for c in range(n_clients)]
    folded = tree
    for c in per_leaf_clients:
        folded = V.vc_asgd_update(folded, c, alpha, use_kernel=True)
    launches_per_leaf = VK.launch_count()

    return {
        # no commas in derived: run.py prints name,us_per_call,derived CSV
        "model": {"us_per_call": 0.0,
                  "derived": f"{n_leaves} leaves / {n_shards} shards x "
                             f"{fp.spec.shard_len} elems / "
                             f"{jax.local_device_count()} "
                             f"{jax.default_backend()} devices",
                  "backend": jax.default_backend(),
                  "device_kind": jax.devices()[0].device_kind},
        "flatten_single": {"us_per_call": round(us_flat_single, 1),
                           "derived": f"padded={F.flatten(tree).spec.padded}"},
        "flatten_sharded": {"us_per_call": round(us_flat_shard, 1),
                            "derived": f"padded={fp.spec.padded}"},
        "assimilate_single": {"us_per_call": round(us_assim_single, 1),
                              "derived": f"{n_clients} clients"},
        "assimilate_sharded": {"us_per_call": round(us_assim_shard, 1),
                               "derived": f"speedup={us_assim_single / max(us_assim_shard, 1e-9):.2f}x"},
        "pallas_launches": {"us_per_call": 0.0,
                            "derived": f"sharded={launches_shard} "
                                       f"single={launches_single} "
                                       f"per_leaf={launches_per_leaf}"},
        "_launches": {"sharded": launches_shard, "single": launches_single,
                      "per_leaf": launches_per_leaf},
    }


def bench_sharded_flat(*, n_shards: int = 4, write_json: bool = True
                       ) -> Dict[str, Dict]:
    """ShardedFlat: the partitioned bus (core/flat.py ShardedTreeSpec +
    runtime/sharding.py shard_map ops) against the single-host flat path —
    flatten/assimilate wall-clock and pallas launch counts on a pod mesh
    of ``n_shards`` devices.  With that many devices it measures on them
    in this process; otherwise on a CPU mesh of ``n_shards`` virtual
    devices in a ``JAX_PLATFORMS=cpu`` child, which needs no device this
    process holds.  The "model" row names the backend and device kind.
    Writes results/BENCH_sharded_flat.json."""
    if jax.device_count() >= n_shards:
        out = _bench_sharded_flat_impl(n_shards)
    else:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + f" --xla_force_host_platform_device_count="
                              f"{n_shards}").strip()
        src = Path(__file__).resolve().parents[1] / "src"
        env["PYTHONPATH"] = (f"{src}{os.pathsep}"
                             + env.get("PYTHONPATH", ""))
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.kernel_bench",
             "--emit-sharded-flat", str(n_shards)],
            capture_output=True, text=True, env=env,
            cwd=Path(__file__).resolve().parents[1], timeout=600)
        if proc.returncode != 0:
            raise RuntimeError(f"sharded_flat subprocess failed:\n"
                               f"{proc.stderr[-3000:]}")
        out = json.loads(proc.stdout)
    if write_json:
        results = Path(__file__).resolve().parents[1] / "results"
        results.mkdir(exist_ok=True)
        (results / "BENCH_sharded_flat.json").write_text(
            json.dumps(out, indent=1))
    return out


if __name__ == "__main__":
    # subprocess entry for bench_sharded_flat's CPU pod mesh
    if len(sys.argv) >= 3 and sys.argv[1] == "--emit-sharded-flat":
        print(json.dumps(_bench_sharded_flat_impl(int(sys.argv[2]))))
    else:
        raise SystemExit("usage: kernel_bench.py --emit-sharded-flat N")
