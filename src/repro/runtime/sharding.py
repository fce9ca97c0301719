"""Sharding planner: decides, per architecture and mesh, how every parameter
and activation is laid out (DESIGN.md §6).

Divisibility is engineered, never assumed (JAX rejects non-divisible
shardings):

* attention: head-TP when ``n_heads % tp == 0`` (KV heads sharded too when
  they divide, else replicated); context-parallel otherwise; fully local for
  tiny models (whisper),
* FFN: always TP over ``model`` (every assigned d_ff divides 16),
* embeddings: vocab over ``model`` (padded to a multiple of 16),
* FSDP: the non-TP dim of every >=2D parameter is sharded over ``data``
  when divisible,
* decode caches: heads over ``model`` when KV divides, else the two-tier
  chunk-sharded layout (seq over ``model``).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.models.common import ModelConfig
from repro.models.plan import NullPlan


def _divides(n: int, k: int) -> bool:
    return k > 0 and n % k == 0


@dataclass
class MeshPlan(NullPlan):
    """Concrete plan for (cfg, mesh).  ``act`` applies sharding constraints;
    ``param_spec`` assigns PartitionSpecs to the parameter pytree."""
    mesh: Mesh = None
    cfg: ModelConfig = None
    data_axis: Any = "data"          # may be ("pod", "data") for multi-pod DP
    model_axis: str = "model"
    tp: int = 1
    dp: int = 1
    fsdp: bool = True
    kv_sharded: bool = False         # kv heads divide tp
    cache_mode: str = "seq"          # "heads" | "seq"

    # ------------------------------------------------------------------
    @classmethod
    def build(cls, cfg: ModelConfig, mesh: Mesh,
              data_axis="data", model_axis="model",
              attn_mode: Optional[str] = None,
              decode_batch: Optional[int] = None,
              moe_ep: bool = False,
              zero_dp: bool = False) -> "MeshPlan":
        """zero_dp: fold the model axis into data — pure DP with ZeRO-style
        fully-sharded params/optimizer, replicated compute (the right plan
        for small-dense models where TP activation all-reduces dominate)."""
        if zero_dp:
            data_axis = (*_tup(data_axis), *_tup(model_axis))
            model_axis = None
            attn_mode = attn_mode or "local"
        tp = int(np.prod([mesh.shape[a] for a in _tup(model_axis)]))
        dp = int(np.prod([mesh.shape[a] for a in _tup(data_axis)]))
        if moe_ep:
            assert cfg.moe is not None and cfg.moe.n_virtual % dp == 0, \
                "set cfg.moe.ep_virtual so n_virtual divides the data axis " \
                "(use ep_tune)"
        if attn_mode is None:
            if cfg.d_model < 1024:
                attn_mode = "local"          # tiny model: replicate attention
            elif _divides(cfg.n_heads, tp):
                attn_mode = "head_tp"
            else:
                attn_mode = "cp"
        kv_sharded = attn_mode == "head_tp" and _divides(cfg.n_kv_heads, tp)
        cache_mode = "heads" if kv_sharded else "seq"
        chunks = tp if cache_mode == "seq" else 1
        # batch-1 long-context decode: nothing to shard over `data`, so the
        # cache chunk dim takes BOTH axes (seq sharded 256/512-way)
        if (decode_batch is not None and not _divides(decode_batch, dp)
                and cache_mode == "seq"):
            chunks = tp * dp
        return cls(mesh=mesh, cfg=cfg, data_axis=data_axis,
                   model_axis=model_axis, tp=tp, dp=dp,
                   attn_mode=attn_mode, cp=(tp if attn_mode == "cp" else 1),
                   kv_sharded=kv_sharded, cache_mode=cache_mode,
                   cache_chunks=chunks, moe_ep=moe_ep,
                   ep=(dp if moe_ep else 1))

    # ------------------------------------------------------------------
    def _axis_size(self, a) -> int:
        return int(np.prod([self.mesh.shape[x] for x in _tup(a)]))

    def _fit(self, spec: P, shape) -> P:
        """Drop sharding on any dim the shape cannot divide (e.g. batch=1)."""
        out = []
        for i, a in enumerate(spec):
            if a is None or i >= len(shape):
                out.append(a)
                continue
            out.append(a if shape[i] % self._axis_size(a) == 0 else None)
        return P(*out)

    @property
    def chunk_axes(self):
        """Mesh axes carrying the decode-cache chunk dim."""
        if self.cache_chunks > self.tp:
            return (*_tup(self.data_axis), *_tup(self.model_axis))
        return self.model_axis

    # ------------------------------------------------------------------
    def _ns(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def act(self, x, kind: str):
        spec = self.act_spec(kind, getattr(x, "ndim", None))
        if spec is None:
            return x
        spec = self._fit(spec, x.shape)
        return jax.lax.with_sharding_constraint(x, self._ns(spec))

    def act_spec(self, kind: str, ndim: Optional[int] = None) -> Optional[P]:
        D, M = self.data_axis, self.model_axis
        table = {
            "bsd": P(D, None, None),
            "enc_bsd": P(D, None, None),
            "cp_bpsd": P(D, M, None, None),
            "q_bshd": P(D, None, M if self.attn_mode == "head_tp" else None,
                        None),
            "kv_bshd": P(D, None, M if self.kv_sharded else None, None),
            "q_bpshd": P(D, M, None, None, None),
            "kv_rep": P(D, None, None, None),
            "kv_gather": P(D, M, None, None, None, None),
            "logits": P(D, None, M),
            "dec_x": P(D, None),
            "dec_q": P(D, M if self.kv_sharded else None, None),
            "dec_logits": P(D, M),
            "cache_old": (P(D, M, None, None, None)
                          if self.cache_mode == "heads"
                          else P(D, None, M, None, None)),
            "cache_old_L": (P(None, D, M, None, None, None)
                            if self.cache_mode == "heads"
                            else P(None, D, None, M, None, None)),
            # expert-parallel MoE layouts
            "ep_tokens": P(D, None, None),
            "ep_dispatched": P(D, None, None, None, None),
            "ep_returned": P(D, None, None, None),
            "ep_w_in": P(D, None, None, M),
            "ep_w_out": P(D, None, M, None),
        }
        return table.get(kind)

    # ------------------------------------------------------------------
    # parameter shardings (path-pattern rules)
    # ------------------------------------------------------------------
    def param_spec(self, path: str, shape: Tuple[int, ...]) -> P:
        D, M = self.data_axis, self.model_axis
        tp, dp = self.tp, self.dp
        name = path.split("/")[-1]

        def fsdp_ok(dim: int) -> bool:
            return self.fsdp and _divides(shape[dim], dp) and \
                int(np.prod(shape)) >= 65536

        def with_fsdp(spec: Tuple, free_dim: int) -> P:
            s = list(spec)
            if s[free_dim] is None and fsdp_ok(free_dim):
                s[free_dim] = D
            return P(*s)

        if len(shape) <= 1:
            return P(*([None] * len(shape)))           # 1D / scalars replicated

        attn_tp = self.attn_mode == "head_tp"
        # ---- embeddings ------------------------------------------------
        if name == "table":                            # [Vp, d]
            return with_fsdp((M, None), 1) if _divides(shape[0], tp) else \
                with_fsdp((None, None), 1)
        if name == "unembed":                          # [d, Vp]
            return with_fsdp((None, M), 0)
        if name == "pos_table":
            return P(None, None)
        # ---- attention ---------------------------------------------------
        if name in ("wq",):                            # [d, h*hd]
            return with_fsdp((None, M), 0) if attn_tp else \
                with_fsdp((None, None), 0)
        if name in ("wk", "wv"):                       # [d, kv*hd]
            return with_fsdp((None, M), 0) if self.kv_sharded else \
                with_fsdp((None, None), 0)
        if name == "wo" and "attn" in path:            # [h*hd, d]
            return with_fsdp((M, None), 1) if attn_tp else \
                with_fsdp((None, None), 1)
        # ---- rwkv time-mix (head-TP always: heads divide for rwkv6) ------
        if "rwkv_tm" in path:
            if name in ("wr", "wk", "wv", "wg"):       # [d, d=h*hd]
                return with_fsdp((None, M), 0)
            if name == "wo":                           # [d, d]
                return with_fsdp((M, None), 1)
            if name in ("lora_a", "w_a"):
                return with_fsdp((None, None), 0)
            if name in ("lora_b", "w_b"):
                return P(*([None] * len(shape)))
            if name == "u":
                return P(M, None) if _divides(shape[0], tp) else P(None, None)
            return P(*([None] * len(shape)))
        if "rwkv_cm" in path:
            if name == "wk":                           # [d, f]
                return with_fsdp((None, M), 0)
            if name == "wv":                           # [f, d]
                return with_fsdp((M, None), 1)
            if name == "wr":
                return with_fsdp((None, None), 0)
        # ---- mamba --------------------------------------------------------
        if "mamba" in path:
            if name == "in_proj":                      # [d, 2*di]
                return with_fsdp((None, M), 0)
            if name == "conv_w":                       # [dc, di]
                return P(None, M)
            if name == "x_proj":                       # [di, dtr+2ds]
                return P(M, None)
            if name == "dt_proj":                      # [dtr, di]
                return P(None, M)
            if name == "a_log":                        # [di, ds]
                return P(M, None)
            if name == "out_proj":                     # [di, d]
                return with_fsdp((M, None), 1)
        # ---- MoE ----------------------------------------------------------
        if name == "router":                           # [d, e]
            return with_fsdp((None, None), 0)
        if "moe" in path and name in ("wi", "wg"):     # [E, d, fv]
            if self.moe_ep:
                return P(D, None, M)                   # experts over data (EP)
            s = [None, None, M]
            if fsdp_ok(1):
                s[1] = D
            return P(*s)
        if "moe" in path and name == "wo":             # [E, fv, d]
            if self.moe_ep:
                return P(D, M, None)
            s = [None, M, None]
            if fsdp_ok(2):
                s[2] = D
            return P(*s)
        # ---- dense mlp ----------------------------------------------------
        if name in ("wi", "wg"):                       # [d, f]
            return with_fsdp((None, M), 0)
        if name == "wo":                               # [f, d]
            return with_fsdp((M, None), 1)
        if name in ("w1", "w2"):                       # vis_proj
            return with_fsdp((None, None), 0)
        return P(*([None] * len(shape)))

    def param_shardings(self, params_tree) -> Any:
        """Pytree of NamedShardings matching params (stacked scan dims get a
        leading None)."""
        def spec_for(path, leaf):
            pstr = "/".join(_key_str(k) for k in path)
            shape = leaf.shape
            # scan-stacked group params carry a leading repeats dim
            stacked = pstr.startswith("group") or pstr.split("/")[0] in ("enc", "dec")
            if stacked:
                inner = self.param_spec(pstr, shape[1:])
                return self._ns(P(None, *inner))
            return self._ns(self.param_spec(pstr, shape))

        return jax.tree_util.tree_map_with_path(spec_for, params_tree)

    # ------------------------------------------------------------------
    def batch_shardings(self, batch_tree, lead_dims: int = 0) -> Any:
        """lead_dims: unsharded leading dims (e.g. 1 for [accum, b, ...])."""
        D = self.data_axis

        def spec_for(leaf):
            nd = len(leaf.shape)
            spec = P(*([None] * lead_dims), D,
                     *([None] * (nd - 1 - lead_dims)))
            return self._ns(self._fit(spec, leaf.shape))

        return jax.tree.map(spec_for, batch_tree)

    def cache_shardings(self, cache_tree) -> Any:
        """Decode-cache shardings: dispatch on the state NamedTuple types,
        padding leading (scan-stack / layer) dims with None."""
        from repro.models.layers import DecodeCache
        from repro.models.mamba import MambaState
        from repro.models.rwkv import RWKVState
        from repro.models.whisper import CrossCache, WhisperDecCache
        D, M = self.data_axis, self.model_axis

        def pad(leaf, spec):
            nd = len(leaf.shape)
            full = (*([None] * (nd - len(spec))), *spec)[-nd:]
            return self._ns(self._fit(P(*full), leaf.shape))

        CH = self.chunk_axes

        def walk(node):
            if isinstance(node, DecodeCache):
                old = ((D, M, None, None, None) if self.cache_mode == "heads"
                       else (D, None, CH, None, None))
                return DecodeCache(
                    k_old=pad(node.k_old, old), v_old=pad(node.v_old, old),
                    old_pos=pad(node.old_pos, (None, None)),
                    k_rec=pad(node.k_rec, (D, None, None, None)),
                    v_rec=pad(node.v_rec, (D, None, None, None)),
                    rec_pos=pad(node.rec_pos, (None,)))
            if isinstance(node, MambaState):
                return MambaState(conv=pad(node.conv, (D, M, None)),
                                  ssm=pad(node.ssm, (D, M, None)))
            if isinstance(node, RWKVState):
                hs = (self.cfg.rwkv is not None and
                      _divides(self.cfg.d_model // self.cfg.rwkv.head_dim,
                               self.tp))
                wkv = (D, M, None, None) if hs else (D, None, None, None)
                return RWKVState(wkv=pad(node.wkv, wkv),
                                 tm_prev=pad(node.tm_prev, (D, None)),
                                 cm_prev=pad(node.cm_prev, (D, None)))
            if isinstance(node, CrossCache):
                return CrossCache(k=pad(node.k, (D, None, None, None)),
                                  v=pad(node.v, (D, None, None, None)))
            if isinstance(node, WhisperDecCache):
                return WhisperDecCache(self_cache=walk(node.self_cache),
                                       cross=walk(node.cross))
            if isinstance(node, (tuple, list)):
                return type(node)(walk(c) for c in node)
            raise TypeError(f"unknown cache node {type(node)}")

        return walk(cache_tree)


# ---------------------------------------------------------------------------
# ShardedFlat: the FlatParams bus (core/flat.py) partitioned over a mesh
# axis.  With a ShardedTreeSpec layout every device owns one contiguous
# BLOCK-padded segment, so the fused flat kernels (Eq. 1/2, Adam, EASGD —
# all elementwise over the bus) run PER SHARD under shard_map with no
# gather, and their results are bit-identical to the single-host flat pass
# at every shard count (tests/test_sharded_flat.py asserts this).
# ---------------------------------------------------------------------------

from jax import shard_map  # noqa: E402


def flat_sharding(mesh: Mesh, axis: str = "pod") -> NamedSharding:
    """NamedSharding placing a 1-D flat buffer as contiguous per-device
    segments along ``axis`` (replicated over any other mesh axes)."""
    return NamedSharding(mesh, P(axis))


def _check_shardable(buf_len: int, mesh: Mesh, axis: str) -> int:
    a = int(mesh.shape[axis])
    if buf_len % a:
        raise ValueError(
            f"flat buffer of {buf_len} elements does not divide the "
            f"{a}-way mesh axis {axis!r}; lay it out with "
            f"flat.shard_spec/flatten_sharded(n_shards={a})")
    return a


def shard_flat(fp, mesh: Mesh, axis: Optional[str] = None):
    """Place a FlatParams' buffer on the mesh: each device gets its own
    contiguous segment.  ``axis`` defaults to the ShardedTreeSpec's axis."""
    from repro.core import flat as F
    if axis is None:
        axis = fp.spec.axis if isinstance(fp.spec, F.ShardedTreeSpec) \
            else "pod"
    _check_shardable(fp.buf.size, mesh, axis)
    return fp.with_buf(jax.device_put(fp.buf, flat_sharding(mesh, axis)))


def _weights_arr(weights) -> jnp.ndarray:
    if isinstance(weights, jnp.ndarray):
        return weights.astype(jnp.float32)
    return jnp.stack([jnp.asarray(w, jnp.float32).reshape(())
                      for w in weights])


def sharded_lerp_flat(server_buf, client_buf, alpha, mesh: Mesh,
                      axis: str = "pod", *, use_kernel: bool = False):
    """Eq. 1 per shard: every device lerps its own segment."""
    _check_shardable(server_buf.size, mesh, axis)
    a = jnp.asarray(alpha, jnp.float32)

    def local(s, c, a_):
        if use_kernel:
            from repro.kernels import ops as K
            return K.fused_lerp_flat(s, c, a_)
        from repro.kernels import ref as R
        return R.vc_asgd_lerp(s, c, a_)

    return shard_map(local, mesh=mesh, in_specs=(P(axis), P(axis), P()),
                     out_specs=P(axis), check_vma=False)(
        server_buf, client_buf, a)


def sharded_assimilate_flat(server_buf, clients_buf, weights, mesh: Mesh,
                            axis: str = "pod", *, use_kernel: bool = False):
    """Eq. 2 per shard: server [N] + clients [n, N] -> [N], each device
    reducing its own contiguous segment over all n client streams in
    arrival order — the same fold as kernels assimilate_flat, so the
    result is bit-identical to the single-host flat pass."""
    _check_shardable(server_buf.size, mesh, axis)
    n = int(clients_buf.shape[0])
    w = _weights_arr(weights)
    if w.shape[0] != n + 1:
        raise ValueError(f"need {n + 1} weights, got {w.shape[0]}")

    def local(w_, s, c):
        if use_kernel:
            from repro.kernels import ops as K
            return K.fused_assimilate_flat(s, c, [w_[i] for i in range(n + 1)])
        acc = w_[0] * s.astype(jnp.float32)
        for j in range(n):
            acc = acc + w_[j + 1] * c[j].astype(jnp.float32)
        return acc.astype(s.dtype)

    return shard_map(local, mesh=mesh,
                     in_specs=(P(), P(axis), P(None, axis)),
                     out_specs=P(axis), check_vma=False)(
        w, server_buf, clients_buf)


def sharded_adam_update_flat(p_buf, g_buf, m_buf, v_buf, lr, b1, b2, eps,
                             weight_decay, c1, c2, mesh: Mesh,
                             axis: str = "pod", *, use_kernel: bool = False):
    """Fused Adam per shard: each device updates the (p, m, v) lanes of its
    own segment — zero cross-device traffic (scalars are replicated)."""
    _check_shardable(p_buf.size, mesh, axis)
    # lr/c1/c2 may be traced (schedules, step-dependent bias correction);
    # b1/b2/eps/weight_decay are static hyperparameters and stay Python
    # floats (ref.adam_update branches on weight_decay's truthiness)
    scal = _weights_arr([lr, c1, c2])

    def local(sc, p, g, m, v):
        if use_kernel:
            from repro.kernels import ops as K
            return K.fused_adam_flat(p, g, m, v, sc[0], b1, b2, eps,
                                     weight_decay, sc[1], sc[2])
        from repro.kernels import ref as R
        return R.adam_update(p, g, m, v, lr=sc[0], b1=b1, b2=b2,
                             eps=eps, c1=sc[1], c2=sc[2],
                             weight_decay=weight_decay)

    blk = P(axis)
    return shard_map(local, mesh=mesh,
                     in_specs=(P(), blk, blk, blk, blk),
                     out_specs=(blk, blk, blk), check_vma=False)(
        scal, p_buf, g_buf, m_buf, v_buf)


def sharded_broadcast_flat(server_buf, n_pods: int, mesh: Mesh,
                           axis: str = "pod"):
    """Redistribution leg per shard: server [N] -> islands [n_pods, N]
    with every device broadcasting ONLY its own contiguous segment (no
    gather — the output stays sharded along ``axis`` on the bus dim).
    Values are plain copies, so the result is bit-identical to the
    single-host ``broadcast_to`` at every pod count."""
    _check_shardable(server_buf.size, mesh, axis)

    def local(s):
        return jnp.broadcast_to(s[None], (n_pods,) + s.shape)

    return shard_map(local, mesh=mesh, in_specs=(P(axis),),
                     out_specs=P(None, axis), check_vma=False)(server_buf)


def sharded_easgd_flat(center_buf, replicas_buf, beta, mesh: Mesh,
                       axis: str = "pod", *, use_kernel: bool = False):
    """Fused elastic EASGD round per shard: center [N] + replicas [n, N]
    updated segment-by-segment, no gather."""
    _check_shardable(center_buf.size, mesh, axis)
    b = jnp.asarray(beta, jnp.float32)

    def local(c, x, b_):
        if use_kernel:
            from repro.kernels import ops as K
            return K.fused_easgd_flat(c, x, b_)
        from repro.kernels import ref as R
        return R.easgd_elastic(c, x, b_)

    return shard_map(local, mesh=mesh,
                     in_specs=(P(axis), P(None, axis), P()),
                     out_specs=(P(axis), P(None, axis)), check_vma=False)(
        center_buf, replicas_buf, b)


def ep_tune(cfg: ModelConfig, dp: int) -> ModelConfig:
    """Set moe.ep_virtual so n_experts * v divides the dp-way EP axis and
    the per-expert f dim splits evenly."""
    import dataclasses
    if cfg.moe is None:
        return cfg
    e, f = cfg.moe.n_experts, cfg.moe.d_ff_expert
    v = 1
    while (e * v) % dp != 0 or f % v != 0:
        v += 1
        if v > dp:
            raise ValueError(f"no virtual factor for e={e}, f={f}, dp={dp}")
    return cfg.replace(moe=dataclasses.replace(cfg.moe, ep_virtual=v))


def _tup(x):
    if x is None:
        return ()
    return x if isinstance(x, tuple) else (x,)


def _key_str(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    if hasattr(k, "name"):
        return str(k.name)
    return str(k)


def _is_stacked(names) -> bool:
    return bool(names) and names[0].startswith("group")
