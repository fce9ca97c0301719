"""Compile the main path for a described TPU v5e, with no chip attached.

Mosaic refuses kernels that interpret mode runs happily (a block that is
not (8, 128)-aligned, a rank-1 block off the vector tiling, an op it
cannot lower), and the TPU compiler refuses a round that does not fit
16 GB.  These tests run both compilers here at the real sizes: every
flat-bus kernel at N = 2^24, and the one-island VC round of
``chip_smoke.py`` (internlm2-1.8b, one chip's share) at batch 8 x 1024.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the worker that runs this file
keeps it until it exits.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import internlm2_1_8b
from repro.kernels import quantize as QZ
from repro.kernels import topk_mask as TM
from repro.kernels import vc_asgd_update as VK
from repro.launch.train import build_round_step
from repro.optim import Adam

N = 1 << 24
V5E_HBM = 16 * 10 ** 9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    cache_was = jax.config.jax_enable_compilation_cache
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        # no skip: a topology that cannot be described fails every test
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_cases():
    lo = 0x3F800000                           # bits of 1.0f
    return {
        "vc_asgd_lerp_flat": (
            lambda s, c: VK.vc_asgd_lerp_flat(s, c, 0.9, interpret=False),
            [(N,), (N,)]),
        "vc_asgd_dc_lerp_flat": (
            lambda *a: VK.vc_asgd_dc_lerp_flat(*a, 0.9, 0.04,
                                               interpret=False),
            [(N,)] * 4),
        "assimilate_flat": (
            lambda s, c: VK.assimilate_flat(s, c, [0.19, 0.081, 0.729],
                                            interpret=False),
            [(N,), (2, N)]),
        "adam_update_flat": (
            lambda *a: VK.adam_update_flat(*a, 1e-3, 0.9, 0.999, 1e-8, 0.0,
                                           0.1, 0.001, interpret=False),
            [(N,)] * 4),
        "easgd_elastic_flat": (
            lambda c, x: VK.easgd_elastic_flat(c, x, 0.1, interpret=False),
            [(N,), (2, N)]),
        "quantize_int8": (
            lambda x: QZ.quantize_int8(x, interpret=False), [(N,)]),
        "blocked_topk_stats": (
            lambda x: TM.blocked_topk_stats(x, lo, interpret=False),
            [(N,)]),
        "threshold_sparsify_exact": (
            lambda x, ts: TM.threshold_sparsify_exact(x, 0.5, ts, 3,
                                                      interpret=False),
            [(N,), ((N // TM.BLOCK,), jnp.int32)]),
    }


@pytest.mark.parametrize("name", sorted(_kernel_cases()))
def test_flat_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = _kernel_cases()[name]
    args = [jax.ShapeDtypeStruct(*(s if isinstance(s[0], tuple)
                                   else (s, jnp.float32)),
                                 sharding=one_chip) for s in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_dequantize_int8_compiles_for_v5e(one_chip):
    q = jax.ShapeDtypeStruct((N,), jnp.int8, sharding=one_chip)
    s = jax.ShapeDtypeStruct((N // QZ.QBLOCK,), jnp.float32,
                             sharding=one_chip)
    hlo = jax.jit(lambda q, s: QZ.dequantize_int8(q, s, N, interpret=False)
                  ).lower(q, s).compile().as_text()
    assert "tpu_custom_call" in hlo


def _compile_round(topo, n_pods):
    """The trainer's round for internlm2-1.8b's one-chip share, one
    island per described chip, 2 local steps at batch 8 x seq 1024."""
    cfg = internlm2_1_8b.chip_share()
    mesh = Mesh(np.asarray(topo.devices[:n_pods]).reshape(n_pods, 1, 1),
                ("pod", "data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 3)
    opt = Adam(lr=1e-3)
    model, _, step, _ = build_round_step(cfg, mesh, n_pods, 2, opt)
    params = model.param_specs()
    islands = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n_pods, *s.shape), s.dtype), params)
    opts = jax.eval_shape(jax.vmap(opt.init), islands)
    rep = NamedSharding(mesh, P())
    batches = {"tokens": jax.ShapeDtypeStruct((n_pods, 2, 8, 1024),
                                              jnp.int32, sharding=rep)}
    compiled = step.lower(params, islands, opts, batches,
                          jax.ShapeDtypeStruct((), jnp.float32),
                          jax.ShapeDtypeStruct((n_pods,), jnp.bool_)
                          ).compile()
    return compiled, (params, islands, opts)


def _assert_fits_donated(compiled, state, n_pods):
    ma = compiled.memory_analysis()
    # server, islands and Adam state are donated to the round's outputs
    # (per chip: the replicated server whole, one island and its state)
    params, islands, opts = state
    per_chip = _bytes(params) + _bytes((islands, opts)) // n_pods
    assert ma.alias_size_in_bytes >= per_chip
    peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert peak < V5E_HBM


def test_smoke_round_fits_one_v5e(topo):
    """The round chip_smoke.py runs: one island, state donated."""
    compiled, state = _compile_round(topo, 1)
    _assert_fits_donated(compiled, state, 1)


def test_pod_round_fits_four_v5e(topo):
    """The round ``chip_smoke.py --chips 4`` runs: four islands, one per
    chip, with the flat bus sharded over ``pod`` (the islands' buffer is
    re-split from one island per chip to one bus segment per chip)."""
    compiled, state = _compile_round(topo, 4)
    _assert_fits_donated(compiled, state, 4)
    assert " all-to-all(" in compiled.as_text()


def _bytes(tree) -> int:
    return sum(x.size * jnp.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree))
