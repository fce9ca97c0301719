"""Plain float32 reference of a dense decoder-only LM (model ``dense_lm``).

Independent of the program under test: nothing here imports it.  The
parameters are laid out as the program stores them, so that one tree can be
handed to both (a dict per block, every leaf stacked over the layers):

    embed.table [Vp, d], embed.unembed [d, Vp], final_norm.scale (.bias),
    group0[0].{norm1, attn.{wq, wk, wv, wo}, norm2, mlp.{wi, wg, wo}}

The model is pre-norm: RMSNorm or LayerNorm, rotate-half rotary embedding
over the first ``rot`` dimensions of each head, causal softmax attention
with grouped KV heads, a SiLU-gated MLP and an untied output head; the loss
is the mean next-token cross entropy over the real vocabulary.  The VC
round around it (Adam, Eq. 2) is ``bench/vc_ref.py``; what a model module
provides is set out in ``bench/models/__init__.py``.

Every matmul runs at ``Precision.HIGHEST``.  ``cdt`` rounds the values that
the program keeps in its compute dtype (the residual stream, matmul
operands and results, attention probabilities) to a lower precision, and
their gradients with them: None is the float32 reference; ``bfloat16``
rounds both ways to bfloat16, as the program computes; ``float8`` is the
control of the check, float8 e4m3 forward and e5m2 backward, the usual
split of float8 training.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK_ROWS = 4      # rows of a batch that go through the model at a time


@dataclass(frozen=True)
class Dims:
    d: int
    heads: int
    kv_heads: int
    hd: int
    ff: int
    layers: int
    vocab: int
    vocab_padded: int
    layernorm: bool
    eps: float
    theta: float
    rot: int

    @property
    def matmul_params(self) -> int:
        """Parameters that enter a matmul once per token (not the table,
        whose rows are gathered, nor the norms)."""
        attn = self.d * self.hd * (2 * self.heads + 2 * self.kv_heads)
        mlp = 3 * self.d * self.ff
        return self.layers * (attn + mlp) + self.d * self.vocab


def dims(conf: dict) -> Dims:
    """Sizes from a configuration file (transformers config.json keys)."""
    d = conf["hidden_size"]
    heads = conf["num_attention_heads"]
    hd = conf.get("head_dim", d // heads)
    layernorm = conf["run"]["norm"] == "layernorm"
    eps = conf["layer_norm_eps"] if layernorm else conf["rms_norm_eps"]
    rot = int(hd * conf.get("partial_rotary_factor", 1.0))
    v = conf["vocab_size"]
    if conf.get("tie_word_embeddings"):
        raise ValueError("the reference has an untied output head only")
    return Dims(d=d, heads=heads, kv_heads=conf["num_key_value_heads"],
                hd=hd, ff=conf["intermediate_size"],
                layers=conf["num_hidden_layers"], vocab=v,
                vocab_padded=-(-v // 16) * 16, layernorm=layernorm,
                eps=float(eps), theta=float(conf["rope_theta"]),
                rot=rot - rot % 2)


def flops_per_token(dm: Dims, seq: int) -> float:
    """Model FLOPs of one trained token, forward and backward, with no
    recompute: 6 per matmul parameter, plus causal attention's scores and
    weighted sum, 2 matmuls x 2 FLOPs x seq/2 keys x heads*hd, times 3."""
    return 6.0 * dm.matmul_params + 6.0 * dm.layers * dm.heads * dm.hd * seq


def params(dm: Dims) -> int:
    """Parameters of the server: every leaf of the model."""
    attn = dm.d * dm.hd * (2 * dm.heads + 2 * dm.kv_heads)
    norms = dm.d * (2 if dm.layernorm else 1)
    block = attn + 3 * dm.d * dm.ff + 2 * norms
    return dm.layers * block + 2 * dm.vocab_padded * dm.d + norms


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _norm(dm: Dims, lead=()):
    p = {"scale": jnp.ones((*lead, dm.d), jnp.float32)}
    if dm.layernorm:
        p["bias"] = jnp.zeros((*lead, dm.d), jnp.float32)
    return p


def init_weights(dm: Dims, key) -> dict:
    """Seeded float32 weights in the program's layout, on the device.
    Matrices are normal with std sqrt(2 / fan_in), the table and the head
    std 1/sqrt(d); norm scales are 1 and biases 0."""
    L, d, hd = dm.layers, dm.d, dm.hd
    shapes = {
        "wq": (L, d, dm.heads * hd), "wk": (L, d, dm.kv_heads * hd),
        "wv": (L, d, dm.kv_heads * hd), "wo": (L, dm.heads * hd, d),
        "wi": (L, d, dm.ff), "wg": (L, d, dm.ff), "wo_mlp": (L, dm.ff, d),
    }
    ks = dict(zip(list(shapes) + ["table", "unembed"],
                  jax.random.split(key, len(shapes) + 2)))

    def he(name):
        s = shapes[name]
        return jax.random.normal(ks[name], s, jnp.float32) * math.sqrt(
            2.0 / s[1])

    vp = dm.vocab_padded
    block = {"norm1": _norm(dm, (L,)),
             "attn": {n: he(n) for n in ("wq", "wk", "wv", "wo")},
             "norm2": _norm(dm, (L,)),
             "mlp": {"wi": he("wi"), "wg": he("wg"), "wo": he("wo_mlp")}}
    s = 1.0 / math.sqrt(d)
    return {"embed": {
                "table": jax.random.normal(ks["table"], (vp, d)) * s,
                "unembed": jax.random.normal(ks["unembed"], (d, vp)) * s},
            "final_norm": _norm(dm),
            "group0": [block]}


def make_init(dm: Dims) -> Callable:
    """One jitted call: seed words -> weights on the device."""
    @jax.jit
    def init(words):
        key = jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])
        return init_weights(dm, key)
    return init


# ---------------------------------------------------------------------------
# forward and loss
# ---------------------------------------------------------------------------

ROUNDING = {"bfloat16": (jnp.bfloat16, jnp.bfloat16),
            "float8": (jnp.float8_e4m3fn, jnp.float8_e5m2)}


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _round(x, cdt):
    return x.astype(ROUNDING[cdt][0]).astype(jnp.float32)


def _round_fwd(x, cdt):
    return _round(x, cdt), None


def _round_bwd(cdt, _, g):
    return (g.astype(ROUNDING[cdt][1]).astype(jnp.float32),)


_round.defvjp(_round_fwd, _round_bwd)


def _q(x, cdt):
    return x if cdt is None else _round(x, cdt)


def _mm(x, w, cdt):
    return _q(jnp.matmul(_q(x, cdt), _q(w, cdt), precision=HIGHEST), cdt)


def _apply_norm(p, x, dm: Dims):
    if dm.layernorm:
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + dm.eps) * p["scale"] + p["bias"]
    var = (x ** 2).mean(-1, keepdims=True)
    return x * jax.lax.rsqrt(var + dm.eps) * p["scale"]


def _rope(x, dm: Dims):
    """x [b, s, h, hd]: rotate-half over the first dm.rot dimensions."""
    s = x.shape[1]
    rot = dm.rot
    freqs = 1.0 / (dm.theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32)
                                / rot))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : rot // 2], x[..., rot // 2: rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            x[..., rot:]], axis=-1)


def _attention(p, x, dm: Dims, cdt):
    b, s, _ = x.shape
    q = _mm(x, p["wq"], cdt).reshape(b, s, dm.heads, dm.hd)
    k = _mm(x, p["wk"], cdt).reshape(b, s, dm.kv_heads, dm.hd)
    v = _mm(x, p["wv"], cdt).reshape(b, s, dm.kv_heads, dm.hd)
    q, k = _q(_rope(q, dm), cdt), _q(_rope(k, dm), cdt)
    rep = dm.heads // dm.kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST)
    scores = scores / math.sqrt(dm.hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", _q(probs, cdt), v, precision=HIGHEST)
    return _mm(_q(o, cdt).reshape(b, s, dm.heads * dm.hd), p["wo"], cdt)


def nll_sum(params, tokens, dm: Dims, cdt=None, scale=1.0):
    """Summed next-token negative log-likelihood of ``tokens`` [b, s],
    times ``scale`` (one over the batch's tokens: the gradients then have
    the program's scale, which matters where they are rounded)."""
    x = _q(params["embed"]["table"][tokens], cdt)
    blk = params["group0"][0]
    for i in range(dm.layers):
        lp = jax.tree.map(lambda a: a[i], blk)
        x = _q(x + _attention(lp["attn"], _q(_apply_norm(lp["norm1"], x, dm),
                                               cdt), dm, cdt), cdt)
        h = _q(_apply_norm(lp["norm2"], x, dm), cdt)
        m = lp["mlp"]
        hidden = _q(jax.nn.silu(_mm(h, m["wg"], cdt)) * _mm(h, m["wi"], cdt),
                    cdt)
        x = _q(x + _mm(hidden, m["wo"], cdt), cdt)
    h = _q(_apply_norm(params["final_norm"], x, dm), cdt)[:, :-1]
    logits = _mm(h, params["embed"]["unembed"][:, :dm.vocab], cdt)
    tgt = tokens[:, 1:]
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
    return jnp.sum(logz - picked) * scale
