"""The program's side of a dense_lm configuration: the ``ModelConfig`` the
trainer runs, built from the configuration file and nothing else."""
from __future__ import annotations

from bench.models.dense_lm import dims


def program_config(conf: dict):
    """``repro.models.common.ModelConfig`` for ``conf`` (a configuration
    file's contents).  Refuses what the program would run differently."""
    from repro.models.common import BlockSpec, ModelConfig, uniform_groups
    if conf.get("hidden_act") != "silu":
        raise ValueError(f"{conf['name']}: only the SiLU-gated MLP is run")
    if conf.get("bias") or conf.get("use_qkv_bias") or \
            conf.get("qk_layernorm") or conf.get("use_parallel_residual"):
        raise ValueError(f"{conf['name']}: a block variant the reference "
                         f"does not model")
    dm = dims(conf)
    run = conf["run"]
    return ModelConfig(
        arch=conf["name"], family="dense", d_model=dm.d, n_heads=dm.heads,
        n_kv_heads=dm.kv_heads, d_ff=dm.ff, vocab_size=dm.vocab,
        head_dim=dm.hd, layer_groups=uniform_groups(dm.layers, BlockSpec()),
        norm=run["norm"], norm_eps=dm.eps, mlp_act="swiglu",
        rope_theta=dm.theta,
        rope_pct=float(conf.get("partial_rotary_factor", 1.0)),
        tie_embeddings=False, max_seq=conf["max_position_embeddings"],
        param_dtype=run["param_dtype"], compute_dtype=run["compute_dtype"])
