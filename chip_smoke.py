#!/usr/bin/env python3
"""Bring-up smoke of the VC-ASGD round on TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded flat bus on four chips

One chip: trains internlm2-1.8b at its published widths (one chip's
share: 2 of 24 layers, 1/8 of the vocabulary) through
``launch/train.py::train`` — island-local steps, Eq. 2 assimilation on
the flat bus, redistribution, server checkpoint — then restores the
checkpoint and compares it bit for bit, runs every flat-bus Pallas kernel
compiled (never interpreted) against its ``kernels/ref.py`` oracle at the
model's bus size, and makes one ``launch/vc_serve.py`` pass with
compressed uploads and a sharded bus.

Four chips: only the pod path — 4 islands, one per chip, on the
trainer's round (``launch/train.py::build_round_step``), whose bus is
sharded over ``pod`` under shard_map; Eq. 2 on that bus is compared with
the per-leaf oracle
(``runtime/vc_runtime.py::assimilate_islands_per_leaf``) on the same
trained islands, then one whole round runs.

Every phase raises on failure.  The last line of stdout is the JSON
verdict, printed only when every phase passed; without a TPU the script
exits non-zero before doing anything.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ROUNDS = 4
LOCAL_STEPS = 2
BATCH, SEQ = 8, 1024
DENSITY = 0.01          # top-k share of the compressed upload
TOPK_FAST_N = 512 * 8192  # a bus the blocked top-k bracket can resolve


class SmokeError(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeError(what)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_train(cfg, workdir: Path, *, rounds=ROUNDS, batch=BATCH, seq=SEQ):
    """The round through the trainer's entry point; loss must fall."""
    import numpy as np
    from repro.launch.train import train
    t0 = time.time()
    out = train(cfg, ckpt_dir=str(workdir / "ckpt"), islands=1,
                rounds=rounds, local_steps=LOCAL_STEPS, batch=batch, seq=seq,
                mesh_shape=(1, 1), seed=0, log=log)
    losses = out["losses"]
    check(len(losses) == rounds and bool(np.isfinite(losses).all()),
          f"losses not finite: {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall: first {losses[0]} last {losses[-1]}")
    log(f"train: {rounds} rounds x {LOCAL_STEPS} local steps at batch "
        f"{batch} x seq {seq}: loss {losses[0]:.4f} -> {losses[-1]:.4f} "
        f"({time.time() - t0:.1f}s incl. compile)")
    return out["server"]


def phase_checkpoint(server, workdir: Path, rounds=ROUNDS):
    """Restore the last server checkpoint and compare it bit for bit."""
    import jax
    import numpy as np
    from repro.checkpoint import CheckpointManager
    mgr = CheckpointManager(workdir / "ckpt")
    check(mgr.latest_step() == rounds,
          f"newest checkpoint is step {mgr.latest_step()}, want {rounds}")
    like = jax.eval_shape(lambda: server)
    restored, extra, step = mgr.restore_or_init(like, lambda: None)
    check(extra.get("round") == rounds, f"checkpoint extra {extra}")
    n = 0
    for a, b in zip(jax.tree.leaves(server), jax.tree.leaves(restored)):
        ha, hb = np.asarray(a), np.asarray(b)
        check(ha.dtype == hb.dtype and ha.shape == hb.shape,
              f"leaf {ha.dtype}{ha.shape} restored as {hb.dtype}{hb.shape}")
        check(ha.tobytes() == hb.tobytes(), "restored leaf differs in bits")
        n += ha.size
    log(f"checkpoint: step {step} restored bit-identical "
        f"({n} parameters, {len(jax.tree.leaves(server))} leaves)")


def _max_err(a, b) -> float:
    import jax.numpy as jnp
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def phase_kernels(n: int, *, tol: float = 1e-5):
    """Every flat-bus kernel at bus size ``n`` (a BLOCK multiple) against
    its ref.py oracle.  ``tol`` bounds the max abs error for inputs of
    unit scale: a few f32 ulps at the largest magnitudes drawn."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import compression as C
    from repro.kernels import ops as K
    from repro.kernels import ref as R
    from repro.kernels import topk_mask as TM
    from repro.kernels import vc_asgd_update as VK
    from repro.transfer import wire

    keys = iter(jax.random.split(jax.random.PRNGKey(7), 16))
    normal = lambda shape=(n,): jax.random.normal(next(keys), shape,
                                                  jnp.float32)

    def report(name, err, bound=tol):
        log(f"kernel {name}: max abs err {err:.3e} (bound {bound:.0e})")
        check(err <= bound, f"{name} off its oracle by {err}")

    s, c, g, b = normal(), normal(), normal(), normal()
    report("vc_asgd_lerp_flat", _max_err(
        jax.jit(lambda s, c: K.fused_lerp_flat(s, c, 0.9))(s, c),
        jax.jit(lambda s, c: R.vc_asgd_lerp(s, c, 0.9))(s, c)))
    report("vc_asgd_dc_lerp_flat", _max_err(
        jax.jit(lambda *a: K.fused_dc_lerp_flat(*a, 0.9, 0.04))(s, c, g, b),
        jax.jit(lambda *a: R.vc_asgd_dc_lerp(*a, 0.9, 0.04))(s, c, g, b)))

    # Eq. 2 over 3 clients; the oracle folds Eq. 1 in arrival order
    w = [0.271, 0.081, 0.162, 0.486]
    clients = jnp.stack([c, g, b])
    got = jax.jit(lambda s, cl: K.fused_assimilate_flat(s, cl, w))(
        s, clients)

    def eq2(s, cl):
        acc = w[0] * s
        for j in range(cl.shape[0]):
            acc = acc + w[j + 1] * cl[j]
        return acc
    report("assimilate_flat", _max_err(got, jax.jit(eq2)(s, clients)))
    del clients, got

    m, v = 0.1 * normal(), jnp.abs(0.01 * normal())
    hp = dict(lr=3e-3, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01,
              c1=1 - 0.9 ** 4, c2=1 - 0.999 ** 4)
    got = jax.jit(lambda *a: K.fused_adam_flat(
        *a, hp["lr"], hp["b1"], hp["b2"], hp["eps"], hp["weight_decay"],
        hp["c1"], hp["c2"]))(s, g, m, v)
    want = jax.jit(lambda *a: R.adam_update(*a, **hp))(s, g, m, v)
    report("adam_update_flat", max(_max_err(x, y)
                                   for x, y in zip(got, want)))
    del m, v, got, want

    reps = jnp.stack([c, g])
    got = jax.jit(lambda c0, x: K.fused_easgd_flat(c0, x, 0.1))(s, reps)
    want = jax.jit(lambda c0, x: R.easgd_elastic(c0, x, 0.1))(s, reps)
    report("easgd_elastic_flat", max(_max_err(x, y)
                                     for x, y in zip(got, want)))
    del reps, got, want, c, g, b

    # int8 codec: Mosaic and XLA may round x/scale differently at .5
    q, sc = jax.jit(K.quantize_int8)(s)
    q_r, sc_r = jax.jit(R.quantize_int8)(s)
    dq = int(jnp.max(jnp.abs(q.astype(jnp.int32) - q_r.astype(jnp.int32))))
    n_dq = int(jnp.sum(q != q_r))
    rel = float(jnp.max(jnp.abs(sc - sc_r) / sc_r))
    log(f"kernel quantize_int8: {n_dq} of {n} codes off by <= {dq}, "
        f"scales max rel err {rel:.3e}")
    check(dq <= 1 and rel <= 1e-6, "quantize_int8 off its oracle")
    report("dequantize_int8", _max_err(
        jax.jit(lambda q, sc: K.dequantize_int8(q, sc, n))(q, sc),
        jax.jit(lambda q, sc: R.dequantize_int8(q, sc, n))(q, sc)))
    del q, sc, q_r, sc_r

    # blocked top-k: stats words/counts exact, exact-k emit under heavy
    # ties exact, and the whole pipeline (stats -> refine -> emit) exact-k
    lo = jax.lax.bitcast_convert_type(jnp.float32(2.0), jnp.uint32)
    wk, ck = jax.jit(K.blocked_topk_stats)(s, lo)
    wr, cr = jax.jit(R.blocked_topk_stats)(s, lo)
    check(bool(jnp.all(wk == wr)) and bool(jnp.all(ck == cr)),
          "blocked_topk_stats words/counts differ from ref")
    log(f"kernel blocked_topk_stats: words and counts bit-exact "
        f"({int(jnp.sum(ck))} candidates)")

    tied = jnp.round(s * 64.0) / 64.0            # many ties at tau
    tau = jnp.float32(1.5)
    per_blk = jnp.sum((jnp.abs(tied) == tau).reshape(-1, TM.BLOCK),
                      axis=1).astype(jnp.int32)
    tie_start = jnp.cumsum(per_blk) - per_blk
    budget = int(jnp.sum(per_blk)) // 2
    got = jax.jit(K.threshold_sparsify_exact)(tied, tau, tie_start, budget)
    want = jax.jit(R.threshold_sparsify_exact)(tied, tau, tie_start,
                                               budget)
    check(all(bool(jnp.all(x == y)) for x, y in zip(got, want)),
          "threshold_sparsify_exact differs from ref")
    log(f"kernel threshold_sparsify_exact: bit-exact with "
        f"{int(jnp.sum(per_blk))} ties at tau, budget {budget}")
    del tied, got, want

    # the pipeline at the bus size, then at a size where its sampled
    # bracket (2^15 candidates of headroom) is narrow enough for the
    # blocked path: exact top-k either way, the blocked path required
    paths = {}
    for size in (n, min(n, TOPK_FAST_N)):
        x, k = s[:size], size // 100
        VK.reset_launch_count()
        kept, res = K.blocked_topk_sparsify(x, k)
        paths[size] = ("blocked" if VK.launch_count() == 2
                       else "dense fallback")
        nz = kept != 0
        check(int(jnp.sum(nz)) == k, f"kept {int(jnp.sum(nz))} != k={k}")
        check(bool(jnp.all(kept + res == x)), "kept + residual != x")
        kmin = float(jnp.min(jnp.where(nz, jnp.abs(kept), jnp.inf)))
        rmax = float(jnp.max(jnp.abs(res)))
        check(kmin >= rmax, f"a residual ({rmax}) outranks a kept ({kmin})")
        log(f"kernel blocked_topk_sparsify: n={size} exact top-{k} via "
            f"the {paths[size]} path (min kept {kmin:.4f} >= max "
            f"residual {rmax:.4f})")
        del kept, res, nz
    check(paths[min(n, TOPK_FAST_N)] == "blocked",
          "the blocked top-k path never ran")

    # the sparse wire frame: its body is packed on the chip in one buffer
    # (values || scales || indices, kernels/ref.py::pack_body under jit)
    k = n // 100
    rng = np.random.default_rng(0)
    qv = rng.integers(-127, 128, k, dtype=np.int8)
    sv = rng.random(-(-k // 256), dtype=np.float32)
    iv = np.sort(rng.integers(0, n, k)).astype(np.int32)
    frame = wire.encode_sparse(C.CompressedDelta(
        jnp.asarray(qv), jnp.asarray(sv), jnp.asarray(iv), (n,), DENSITY,
        256))
    body = qv.tobytes() + sv.tobytes() + iv.tobytes()
    check(frame.endswith(body),
          "wire body bytes packed on chip differ from host bytes")
    log(f"wire pack_body: the sparse frame's body packed on chip is "
        f"byte-identical to the host's ({len(body)} B)")


def phase_serve(workdir: Path):
    """One vc_serve pass: Coordinator, wire, compression, checkpoint."""
    import io
    from contextlib import redirect_stdout
    from repro.launch.vc_serve import main as serve_main
    buf = io.StringIO()
    t0 = time.time()
    ckpt = workdir / "serve"
    with redirect_stdout(buf):
        rc = serve_main(["--density", str(DENSITY), "--shards", "4",
                         "--ckpt-dir", str(ckpt)])
    for line in buf.getvalue().splitlines():
        log(f"vc_serve | {line}")
    check(rc == 0, f"vc_serve exited {rc}")
    check("results assimilated" in buf.getvalue(), "vc_serve did not finish")
    check(any(ckpt.glob("ckpt_*.msgpack")), "vc_serve wrote no checkpoint")
    log(f"vc_serve: invariants held ({time.time() - t0:.1f}s)")


def phase_pod(cfg, n_pods: int, *, batch=BATCH, seq=SEQ):
    """n_pods islands, one per device, on the trainer's own round
    (``launch/train.py::build_round_step``, whose bus is sharded over
    ``pod`` for more than one island).  First, on the same trained
    islands, Eq. 2 on the sharded bus against the per-leaf oracle (every
    island alive, then island 1 preempted); then one whole round, whose
    redistribution must hand the server back to every island."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.mesh import make_mesh
    from repro.launch.train import build_round_step, place_state, \
        round_batches
    from repro.optim import Adam
    from repro.runtime.vc_runtime import (assimilate_islands_per_leaf,
                                          island_weights,
                                          make_island_assimilator,
                                          make_island_trainer)
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = make_mesh((n_pods, 1, 1), ("pod", "data", "model"))
    opt = Adam(lr=1e-3)
    model, plan, step, shardings = build_round_step(cfg, mesh, n_pods,
                                                    LOCAL_STEPS, opt)
    server_sh, island_sh, opt_sh = shardings
    rep = NamedSharding(mesh, P())
    # the oracle needs the islands between training and Eq. 2, which the
    # round never returns: the round's two halves, jitted apart
    train_islands = jax.jit(
        make_island_trainer(model, plan, LOCAL_STEPS, opt),
        in_shardings=(island_sh, opt_sh, None),
        out_shardings=(island_sh, opt_sh, rep))
    assimilate = jax.jit(
        make_island_assimilator(plan, n_pods, flat_shard_axis="pod"),
        in_shardings=(server_sh, island_sh, rep, rep),
        out_shardings=(server_sh, island_sh))
    oracle = jax.jit(
        lambda s, isl, a, surv: assimilate_islands_per_leaf(
            s, isl, *island_weights(n_pods, a, surv)),
        in_shardings=(server_sh, island_sh, rep, rep),
        out_shardings=server_sh)

    def check_redistributed(server, islands):
        for s_leaf, i_leaf in zip(jax.tree.leaves(server),
                                  jax.tree.leaves(islands)):
            check(bool(jnp.all(i_leaf == s_leaf[None])),
                  "redistributed islands differ from the server")

    t0 = time.time()
    alpha = jnp.float32(0.9)
    with mesh:
        server, islands, opts = place_state(
            model.init(jax.random.PRNGKey(0)), n_pods, opt, shardings)
        batches = round_batches(cfg, n_pods, LOCAL_STEPS, batch, seq,
                                seed=11)
        trained, _, losses = train_islands(islands, opts, batches)
        losses = np.asarray(losses)
        check(bool(np.isfinite(losses).all()), f"island losses {losses}")
        log(f"pod: {n_pods} islands on {n_pods} devices trained "
            f"{LOCAL_STEPS} steps, losses {np.round(losses, 4).tolist()} "
            f"({time.time() - t0:.1f}s incl. compile)")
        for surv in (np.ones(n_pods, bool), np.arange(n_pods) != 1):
            surv = jnp.asarray(surv)
            new_server, new_islands = assimilate(server, trained, alpha,
                                                 surv)
            want = oracle(server, trained, alpha, surv)
            err = max(_max_err(a, b) for a, b in zip(
                jax.tree.leaves(new_server), jax.tree.leaves(want)))
            scale = max(float(jnp.max(jnp.abs(x)))
                        for x in jax.tree.leaves(want))
            log(f"pod: sharded Eq. 2 vs per-leaf oracle, survivors "
                f"{np.asarray(surv).astype(int).tolist()}: max abs err "
                f"{err:.3e} (max |param| {scale:.3e})")
            check(err <= 1e-6 * scale, "sharded Eq. 2 off the oracle")
            check_redistributed(new_server, new_islands)
        del trained, new_server, new_islands, want

        t0 = time.time()
        server, islands, opts, metrics = step(
            server, islands, opts, batches, alpha, jnp.ones(n_pods, bool))
        loss = float(metrics["loss"])
        check(bool(np.isfinite(loss)), f"round loss {loss}")
        check_redistributed(server, islands)
        log(f"pod: the trainer's round on the sharded bus: loss "
            f"{loss:.4f} (islands' mean {float(losses.mean()):.4f}), "
            f"every island handed the server "
            f"({time.time() - t0:.1f}s incl. compile)")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {dev.platform}); refusing to "
              f"report", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro import compile_cache
    from repro.configs import internlm2_1_8b as arch
    from repro.core import flat as F
    from repro.kernels import ops as K

    log(f"device: {dev.platform} {dev.device_kind} x {len(devices)}; "
        f"compile cache {compile_cache.enable()}")
    check(not K._interpret(), "kernels would run in interpret mode")
    published, cfg = arch.config(), arch.chip_share()
    log(f"config: {published.describe()} rope_theta={published.rope_theta:g}")
    log(f"cut: depth {published.n_layers} -> {cfg.n_layers} layers (the "
        f"rest would be further pipeline stages)")
    log(f"cut: vocab {published.vocab_size} -> {cfg.vocab_size} (this "
        f"chip's share of a vocabulary split over {arch.VOCAB_CHIPS} chips)")

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        workdir = Path(tmp)
        if args.chips == 4:
            phase_pod(cfg, 4)
        else:
            server = phase_train(cfg, workdir)
            n = sum(x.size for x in jax.tree.leaves(server))
            phase_checkpoint(server, workdir)
            del server
            padded = -(-n // F.BLOCK) * F.BLOCK
            log(f"bus: {n} parameters, padded to {padded}")
            phase_kernels(padded)
            phase_serve(workdir)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
