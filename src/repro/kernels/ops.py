"""Public jit'd wrappers over the Pallas kernels.

Off the TPU every kernel runs in interpret mode — the kernel body
executes as traced jnp ops, which validates BlockSpecs, index maps and the
kernel math against ref.py; that is the CPU test route and nothing else.
On a TPU backend every call compiles to Mosaic and none is interpreted
(``chip_smoke.py`` asserts it).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import mamba_scan as _ms
from repro.kernels import quantize as _qz
from repro.kernels import rwkv6_scan as _rw
from repro.kernels import topk_mask as _tm
from repro.kernels import vc_asgd_update as _vc


def _interpret() -> bool:
    """True off the TPU (the CPU test route); False on a TPU backend."""
    return jax.default_backend() != "tpu"


def fused_lerp(server, client, alpha):
    """VC-ASGD Eq. 1 on one tensor (pytree mapping handled by callers)."""
    return _vc.vc_asgd_lerp(server, client, alpha, interpret=_interpret())


def fused_dc_lerp(server, client, grad, backup, alpha, lam=0.04):
    return _vc.vc_asgd_dc_lerp(server, client, grad, backup, alpha, lam,
                               interpret=_interpret())


def fused_lerp_flat(server_buf, client_buf, alpha):
    """Eq. 1 over the whole flat bus (core/flat.py) — ONE launch."""
    return _vc.vc_asgd_lerp_flat(server_buf, client_buf, alpha,
                                 interpret=_interpret())


def fused_dc_lerp_flat(server_buf, client_buf, grad_buf, backup_buf, alpha,
                       lam=0.04):
    return _vc.vc_asgd_dc_lerp_flat(server_buf, client_buf, grad_buf,
                                    backup_buf, alpha, lam,
                                    interpret=_interpret())


def fused_assimilate_flat(server_buf, clients_buf, weights):
    """Eq. 2 over [n_clients, N] stacked flat buffers — ONE launch."""
    return _vc.assimilate_flat(server_buf, clients_buf, weights,
                               interpret=_interpret())


def fused_adam_flat(p_buf, g_buf, m_buf, v_buf, lr, b1, b2, eps,
                    weight_decay, c1, c2):
    """Whole-model Adam (params + m/v lanes of the flat bus) — ONE launch."""
    return _vc.adam_update_flat(p_buf, g_buf, m_buf, v_buf, lr, b1, b2, eps,
                                weight_decay, c1, c2, interpret=_interpret())


def fused_easgd_flat(center_buf, replicas_buf, beta):
    """Elastic EASGD round: center [N] + replicas [n, N] — ONE launch."""
    return _vc.easgd_elastic_flat(center_buf, replicas_buf, beta,
                                  interpret=_interpret())


def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    q_block=256, kv_block=256):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, q_block=q_block,
                               kv_block=kv_block, interpret=_interpret())


def wkv6(r, k, v, w, u):
    return _rw.wkv6(r, k, v, w, u, interpret=_interpret())


def mamba_scan(u, dt, B, C, A, D, d_block=128):
    return _ms.mamba_scan(u, dt, B, C, A, D, d_block=d_block,
                          interpret=_interpret())


def quantize_int8(x):
    return _qz.quantize_int8(x, interpret=_interpret())


def dequantize_int8(q, scales, n, out_dtype=jnp.float32):
    return _qz.dequantize_int8(q, scales, n, out_dtype,
                               interpret=_interpret())


def threshold_sparsify(x, tau):
    return _tm.threshold_sparsify(x, tau, interpret=_interpret())


def blocked_topk_stats(x, lo):
    """ONE memory-bound pass: per-block packed candidate words + counts."""
    return _tm.blocked_topk_stats(x, lo, interpret=_interpret())


def threshold_sparsify_exact(x, tau, tie_start, tie_budget):
    """Exact-k kept/residual emit (deterministic under ties at tau)."""
    return _tm.threshold_sparsify_exact(x, tau, tie_start, tie_budget,
                                        interpret=_interpret())


def blocked_topk_sparsify(x, k):
    """Exact global top-k (kept, residual): stats launch + tiny refinement
    + exact-k emit launch; dense fallback when the bracket misses."""
    return _tm.blocked_topk_sparsify(x, k, interpret=_interpret())

