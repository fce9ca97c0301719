"""VC-ASGD at pod scale (DESIGN.md §4): client islands = pods.

Each island holds its own full parameter/optimizer replica (leading ``pod``
dim, sharded over the pod mesh axis; inner dims follow the single-pod
MeshPlan).  One **VC round** =

  1. ``k`` local train steps per island — vmapped over the pod dim, so there
     is NO cross-pod collective inside the round (the paper's asynchronous,
     barrier-free client training),
  2. assimilation — Eq. 2 as a single weighted reduction over the pod axis,
     with a survivor mask: islands that died this round (preemption) simply
     get weight zero and the weights renormalize (fault tolerance is
     algebraic, not protocol-level),
  3. redistribution — the new server copy travels back over pods as a
     per-shard broadcast ON THE FLAT BUS (each device copies only its own
     contiguous segment under shard_map — no gather; the paper's clients
     always start a subtask from the server snapshot).  The protocol
     runtime ships the same segments as per-shard handout frames
     (wire.KIND_SHARD) through the Transport at lease issue.

The optional compressed path ships int8 top-k deltas with error feedback
(core/compression.py) instead of raw weights across the DCN — globally
sparsified over the whole model on the FlatParams bus (core/flat.py), one
compression + one accumulate per island.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import vc_asgd as V
from repro.models.registry import Model
from repro.optim import Adam, clip_by_global_norm
from repro.runtime.sharding import MeshPlan
from repro.transfer.transport import Transport


def island_weights(n_pods: int, alpha: float, survivors: jnp.ndarray
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Eq. 2 weights [w_0..w_{n-1}] (island order = arrival order) with dead
    islands zeroed; returns (w_islands [n_pods], w_server scalar)."""
    j = jnp.arange(n_pods, dtype=jnp.float32)
    w = (1.0 - alpha) * alpha ** (n_pods - 1.0 - j)
    w = w * survivors.astype(jnp.float32)
    return w, 1.0 - w.sum()


def assimilate_flat(server_buf, islands_buf, w, w_s, *,
                    mesh=None, shard_axis=None, use_kernel: bool = False):
    """Eq. 2 over the FLAT bus with survivor masking: server [N] +
    islands [n_pods, N] -> [N].

    Select-before-multiply: a dead island may hold inf/nan (it crashed
    mid-step) and ``0 * inf`` would poison the server, so dead streams are
    zeroed BEFORE the weighted reduction.  The reduction itself is
    elementwise over the bus, so with ``mesh``/``shard_axis`` set it runs
    per contiguous shard segment under shard_map (runtime/sharding.py) —
    no gather, bit-identical to the single-host pass at every pod count.
    ``use_kernel=True`` routes the masked reduction through the fused
    single-launch Pallas kernel (kernels assimilate_flat)."""
    wi = w.reshape((-1, 1)).astype(jnp.float32)
    islands_buf = jnp.where(wi > 0.0, islands_buf.astype(jnp.float32), 0.0)
    if use_kernel:
        n = int(islands_buf.shape[0])
        weights = [w_s] + [w[j] for j in range(n)]
        if mesh is not None:
            from repro.runtime.sharding import sharded_assimilate_flat
            return sharded_assimilate_flat(server_buf, islands_buf, weights,
                                           mesh, shard_axis, use_kernel=True)
        from repro.kernels import ops as K
        return K.fused_assimilate_flat(server_buf, islands_buf, weights)

    # NOT routed through sharding.sharded_assimilate_flat's jnp form: that
    # helper folds client streams SEQUENTIALLY (the kernel's order), while
    # the retained per-leaf oracle (assimilate_islands_per_leaf) reduces
    # with jnp.sum over the pod axis — bit-exactness against the oracle
    # pins this reduction order, sharded and unsharded alike.
    def local(s, isl, w_, ws_):
        wj = w_.reshape((-1, 1)).astype(jnp.float32)
        contrib = jnp.sum(wj * isl.astype(jnp.float32), axis=0)
        return (ws_ * s.astype(jnp.float32) + contrib).astype(s.dtype)

    if mesh is None:
        return local(server_buf, islands_buf, w, w_s)
    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P(shard_axis), P(None, shard_axis),
                                   P(), P()),
                         out_specs=P(shard_axis), check_vma=False)(
        server_buf, islands_buf, w, jnp.asarray(w_s, jnp.float32))


def redistribute_flat(server_buf, n_pods: int, *, mesh=None,
                      shard_axis=None):
    """Step-3 redistribution on the bus: server [N] -> islands
    [n_pods, N] (every island restarts the next round from the server
    snapshot, §III).  With ``mesh``/``shard_axis`` set the broadcast runs
    per contiguous shard segment under shard_map
    (runtime/sharding.py::sharded_broadcast_flat) — each device copies
    only its own segment, no gather — and is bit-identical to the
    single-host broadcast at every pod count (the values are copies
    either way; tests pin it against the per-leaf oracle)."""
    if mesh is None:
        return jnp.broadcast_to(server_buf[None],
                                (n_pods,) + server_buf.shape)
    from repro.runtime.sharding import sharded_broadcast_flat
    return sharded_broadcast_flat(server_buf, n_pods, mesh, shard_axis)


def redistribute_per_leaf(server, islands):
    """Pre-download-leg reference: the per-leaf tree.map broadcast
    make_vc_round used before redistribution moved onto the flat bus.
    Retained as the bit-exactness oracle (tests/test_runtime_vc.py)."""
    return jax.tree.map(
        lambda s, isl: jnp.broadcast_to(s[None], isl.shape).astype(isl.dtype),
        server, islands)


def assimilate_islands_per_leaf(server, islands, w, w_s):
    """Pre-ShardedFlat reference: the per-leaf tree.map merge make_vc_round
    used before the assimilation moved onto the flat bus.  Retained as the
    bit-exactness oracle (tests/test_sharded_flat.py)."""
    n_pods = jax.tree.leaves(islands)[0].shape[0]

    def merge(s, isl):
        wi = w.reshape((n_pods,) + (1,) * (isl.ndim - 1)).astype(jnp.float32)
        contrib = jnp.sum(jnp.where(wi > 0.0,
                                    wi * isl.astype(jnp.float32), 0.0),
                          axis=0)
        return (w_s * s.astype(jnp.float32) + contrib).astype(s.dtype)

    return jax.tree.map(merge, server, islands)


def make_island_trainer(model: Model, plan: MeshPlan, local_steps: int = 4,
                        optimizer=None, clip_norm: float = 1.0):
    """Returns train_islands(islands, opts, batches) -> (islands', opts',
    losses [n_pods]): ``local_steps`` steps per island (scan over steps),
    vmapped over the leading pod dim — NO cross-pod collective (the
    paper's asynchronous, barrier-free client training)."""
    optimizer = optimizer or Adam(lr=3e-4)

    def local_train(params, opt_state, steps_batch):
        def step(carry, batch):
            p, o = carry
            (loss, _), grads = jax.value_and_grad(
                lambda pp: model.loss(pp, batch, plan=plan), has_aux=True)(p)
            grads, _ = clip_by_global_norm(grads, clip_norm)
            p, o = optimizer.update(grads, o, p)
            return (p, o), loss
        (params, opt_state), losses = jax.lax.scan(
            step, (params, opt_state), steps_batch)
        return params, opt_state, losses.mean()

    return jax.vmap(local_train)


def make_island_assimilator(plan: MeshPlan, n_pods: int,
                            flat_shard_axis: Optional[str] = None,
                            use_kernel: bool = False):
    """Returns assimilate(server, islands, alpha, survivors) -> (server',
    islands'): Eq. 2 on the flat bus, then redistribution.

    The trained islands are flattened once into a [n_pods, padded]
    matrix, the server once onto the same layout, and Eq. 2 is ONE masked
    weighted reduction over contiguous buffers (``assimilate_flat``)
    instead of a per-leaf tree walk — the same code path as the
    simulator's schemes.  With ``flat_shard_axis`` set (a mesh axis of
    ``plan.mesh``), the buffers are padded so every device owns a
    contiguous BLOCK-multiple segment and the reduction runs per shard
    under shard_map with no gather.  Every island then restarts from the
    server snapshot via a per-shard broadcast, bit-identical to the
    retained per-leaf broadcast oracle."""
    from repro.core import flat as F
    pad_to = F.BLOCK
    mesh = None
    if flat_shard_axis is not None:
        mesh = plan.mesh
        pad_to = F.BLOCK * int(mesh.shape[flat_shard_axis])

    def assimilate(server, islands, alpha, survivors):
        w, w_s = island_weights(n_pods, alpha, survivors)
        # the scopes name the round's phases in a profiler's trace viewer
        with jax.named_scope("vc.flatten"):
            isl_buf, spec = F.flatten_batched(islands, pad_to=pad_to)
            s_buf = F.flatten_like(server, spec)
        with jax.named_scope("vc.eq2"):
            out_buf = assimilate_flat(s_buf, isl_buf, w, w_s, mesh=mesh,
                                      shard_axis=flat_shard_axis,
                                      use_kernel=use_kernel)
        with jax.named_scope("vc.unflatten"):
            server = F.unflatten(F.FlatParams(out_buf, spec))
        with jax.named_scope("vc.redistribute"):
            isl_out = redistribute_flat(out_buf, n_pods, mesh=mesh,
                                        shard_axis=flat_shard_axis)
        with jax.named_scope("vc.unflatten"):
            return server, F.unflatten_batched(isl_out, spec)

    return assimilate


def make_vc_round(model: Model, plan: MeshPlan, n_pods: int,
                  local_steps: int = 4, optimizer=None,
                  clip_norm: float = 1.0,
                  flat_shard_axis: Optional[str] = None,
                  use_kernel: bool = False):
    """Returns vc_round(server, islands, opts, batches, alpha, survivors)
    -> (server', islands', opts', metrics): island-local training
    (``make_island_trainer``), then Eq. 2 assimilation and redistribution
    on the flat bus (``make_island_assimilator``).

    islands/opts carry a leading [n_pods] dim; batches carry
    [n_pods, local_steps, ...]."""
    train_islands = make_island_trainer(model, plan, local_steps, optimizer,
                                        clip_norm)
    assimilate = make_island_assimilator(plan, n_pods, flat_shard_axis,
                                         use_kernel)

    def vc_round(server, islands, opts, batches, alpha, survivors):
        with jax.named_scope("vc.local_steps"):
            islands, opts, losses = train_islands(islands, opts, batches)
        server, islands = assimilate(server, islands, alpha, survivors)
        return server, islands, opts, {"loss": losses.mean()}

    return vc_round


def island_shardings(model: Model, plan: MeshPlan, n_pods: int,
                     optimizer, pod_axis: str = "pod"):
    """Shardings: server replicated over pod / sharded inner; islands carry a
    leading pod-sharded dim."""
    p_specs = model.param_specs()
    inner = plan.param_shardings(p_specs)

    def lift(ns: NamedSharding) -> NamedSharding:
        return NamedSharding(plan.mesh, P(pod_axis, *ns.spec))

    server_sh = inner
    island_sh = jax.tree.map(lift, inner)
    opt_specs = jax.eval_shape(optimizer.init, p_specs)
    from repro.optim import OptState
    opt_sh = OptState(step=NamedSharding(plan.mesh, P(pod_axis)),
                      m=jax.tree.map(lift, inner),
                      v=jax.tree.map(lift, inner))
    return server_sh, island_sh, opt_sh


def compressed_assimilate(server, islands, alpha, survivors, *,
                          density: float = 0.05, residuals=None,
                          transport: Optional["Transport"] = None):
    """Delta-form Eq. 2 with GLOBAL (whole-model) top-k + int8 compression
    and error feedback — what actually crosses the DCN between pods.

    Flat-bus path (core/flat.py): the server and every island are flattened
    once, each island ships ONE globally-sparsified delta buffer (k chosen
    over the whole model, not per leaf — strictly no worse mass retention
    at equal density), and the weighted Eq. 2 reduction happens on the
    contiguous buffer.  One compression + one accumulate per island instead
    of the per-leaf × per-island loop.  Returns (server', residuals') with
    the same tree-in/tree-out contract as before (residuals island-major).

    With ``transport`` set (any transfer/transport.py ``Transport`` —
    the in-memory loopback or the cross-process broker), each island's
    payload really crosses the wire: encoded to bytes (wire format v1),
    sent, received and decoded before assimilation — the transport's
    stats then hold the REAL per-round transfer sizes.  (Host-level
    path: call it eagerly, not under jit.)"""
    from repro.core import compression as C
    from repro.core import flat as F
    n = islands_leading_dim(islands)
    w, w_s = island_weights(n, alpha, survivors)

    fp = F.flatten(server)
    isl_buf, spec = F.flatten_batched(islands)
    if spec.shapes != fp.spec.shapes:
        raise ValueError("island layout does not match server layout")
    res_buf = (F.flatten_batched(residuals)[0] if residuals is not None
               else None)

    s32 = fp.buf
    out = w_s * s32
    new_res = []
    for j in range(n):
        delta = isl_buf[j] - s32
        payload, r = C.compress_flat(
            delta, density=density, logical_n=spec.n,
            residual=None if res_buf is None else res_buf[j])
        if transport is not None:
            from repro.transfer import wire
            mid = transport.send(wire.encode_sparse(
                payload, residual_norm=float(jnp.linalg.norm(r))))
            payload = wire.decode(transport.recv(mid)).payload
        deq = C.decompress_flat(payload)
        out = out + w[j] * (s32 + deq)
        new_res.append(r)
    server_out = F.unflatten(fp.with_buf(out))
    # residuals carry in f32 (like the per-leaf reference): truncating the
    # error-feedback carry to the params' storage dtype would lose it
    residuals_out = F.unflatten_batched(jnp.stack(new_res), spec,
                                        dtype=jnp.float32)
    return server_out, residuals_out


def compressed_assimilate_per_leaf(server, islands, alpha, survivors, *,
                                   density: float = 0.05, residuals=None):
    """TEST/BENCH ORACLE ONLY (retired from every runtime path): per-leaf
    top-k in a per-leaf × per-island Python loop.  Kept as the
    numerical/perf baseline for the flat path (tests/test_flat.py,
    benchmarks/kernel_bench.py::bench_flat_assimilate); compresses worse
    than the global top-k at equal density."""
    from repro.core import compression as C
    n = islands_leading_dim(islands)
    w, w_s = island_weights(n, alpha, survivors)

    def one_leaf(s, isl, res):
        s32 = s.astype(jnp.float32)
        out = w_s * s32
        new_res = []
        for j in range(n):
            delta = isl[j].astype(jnp.float32) - s32
            if res is not None:
                delta = delta + res[j]
            payload, r = C.compress_delta(delta, density=density)
            deq = C.decompress_delta(payload)
            out = out + w[j] * (s32 + deq)
            new_res.append(r)
        return out.astype(s.dtype), jnp.stack(new_res)

    flat_s, tdef = jax.tree.flatten(server)
    flat_i = jax.tree.leaves(islands)
    flat_r = (jax.tree.leaves(residuals) if residuals is not None
              else [None] * len(flat_s))
    merged, residuals_out = [], []
    for s, isl, r in zip(flat_s, flat_i, flat_r):
        m, nr = one_leaf(s, isl, r)
        merged.append(m)
        residuals_out.append(nr)
    return jax.tree.unflatten(tdef, merged), jax.tree.unflatten(tdef, residuals_out)


def islands_leading_dim(islands) -> int:
    return jax.tree.leaves(islands)[0].shape[0]
