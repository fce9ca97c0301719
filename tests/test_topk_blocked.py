"""Blocked top-k selection + fused wire-encode properties.

The selection contract: ``select_topk`` is EXACT top-|x| (deterministic
under ties — lowest index wins, same as ``lax.top_k``), returning exactly
k ASCENDING indices on every path (dense fallback and sampled-bracket
fast path alike); ``blocked_topk_sparsify`` emits (kept, residual) with
kept + residual == x BIT-exact.  The encode contract: the fused pack
writes the same ``values || scales || indices`` body bytes the pre-PR
encoder produced with separate numpy ``tobytes()`` copies — sparse wire
frames are byte-identical at equal (tau, k).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compression as C
from repro.kernels import ops as K
from repro.kernels import ref as R
from repro.transfer import wire

RNG = jax.random.PRNGKey(7)

# one fast-path size (sampled bracket + blocked kernels) and two fallback
# sizes; the fast path needs n >= _MIN_FAST_N and n % 32 == 0
FAST_N = C._MIN_FAST_N
CASES = [(4096, 409), (65536, 655), (FAST_N, FAST_N // 20)]


def _oracle_idx(x, k):
    """Sort oracle with the lax.top_k tie rule: by (|x| desc, index asc)."""
    mag = np.abs(np.asarray(x, np.float32))
    order = np.lexsort((np.arange(mag.size), -mag.astype(np.float64)))
    return np.sort(order[:k])


def _tie_heavy(n):
    """Coarsely quantized magnitudes: thousands of exact ties, including
    across the selection boundary."""
    x = jax.random.normal(RNG, (n,), jnp.float32)
    return jnp.round(x * 4.0) / 4.0


@pytest.mark.parametrize("n,k", CASES)
def test_select_topk_exact_vs_sort_oracle(n, k):
    x = jax.random.normal(jax.random.fold_in(RNG, n), (n,), jnp.float32)
    idx = np.asarray(C.select_topk(x, k))
    assert idx.shape == (k,)
    assert (np.diff(idx) > 0).all()                  # ascending, unique
    np.testing.assert_array_equal(idx, _oracle_idx(x, k))


@pytest.mark.parametrize("n,k", CASES)
def test_select_topk_deterministic_k_under_ties(n, k):
    x = _tie_heavy(n)
    idx = np.asarray(C.select_topk(x, k))
    assert idx.shape == (k,)                          # exactly k, always
    np.testing.assert_array_equal(idx, _oracle_idx(x, k))


def test_select_topk_all_zero_input():
    n, k = CASES[0]
    idx = np.asarray(C.select_topk(jnp.zeros((n,), jnp.float32), k))
    np.testing.assert_array_equal(idx, np.arange(k))  # tie rule: lowest


@pytest.mark.parametrize("n,k", CASES)
def test_blocked_sparsify_kept_plus_residual_bit_exact(n, k):
    x = jax.random.normal(jax.random.fold_in(RNG, 2 * n + 1), (n,),
                          jnp.float32)
    kept, res = K.blocked_topk_sparsify(x, k)
    kb = np.asarray(kept).view(np.uint32)
    rb = np.asarray(res).view(np.uint32)
    xb = np.asarray(x).view(np.uint32)
    # reconstruction is BIT-exact: kept entries carry x with res == 0,
    # dropped entries carry res == x with kept == 0
    np.testing.assert_array_equal(
        np.asarray(kept + res).view(np.uint32), xb)
    idx = _oracle_idx(x, k)
    mask = np.zeros(n, bool)
    mask[idx] = True
    np.testing.assert_array_equal(kb[~mask], 0)
    np.testing.assert_array_equal(kb[mask], xb[mask])
    np.testing.assert_array_equal(rb[mask], 0)


@pytest.mark.parametrize("n,k", CASES)
def test_retained_mass_matches_sort_oracle(n, k):
    x = _tie_heavy(n)
    kept, _ = K.blocked_topk_sparsify(x, k)
    got = np.sort(np.abs(np.asarray(kept)[np.asarray(kept) != 0.0]))
    mag = np.sort(np.abs(np.asarray(x)))[-k:]
    # same multiset of magnitudes as the sort oracle's top k (ties may
    # leave zeros out of `kept`'s nonzero set only if x itself had a
    # zero in the top k, impossible for k < count of nonzeros)
    np.testing.assert_array_equal(got, mag[mag != 0.0])


def test_fused_encode_byte_identity_with_pre_pr_layout():
    """wire.encode(sparse) body == values.tobytes() || scales.tobytes()
    || indices.tobytes() — the exact byte layout the pre-PR encoder
    emitted with three separate host copies."""
    n, k = 8192, 819                              # k > block: 4 scale groups
    x = jax.random.normal(jax.random.fold_in(RNG, 99), (n,), jnp.float32)
    payload, _ = C.compress_flat(x, density=k / n)
    v = np.asarray(payload.values)
    s = np.asarray(payload.scales)
    i = np.asarray(payload.indices)
    expected_body = v.tobytes() + s.tobytes() + i.tobytes()
    frame = wire.encode(payload, round=3, residual_norm=0.5)
    assert frame.endswith(expected_body)
    msg = wire.decode(frame)
    np.testing.assert_array_equal(np.asarray(msg.payload.values), v)
    np.testing.assert_array_equal(np.asarray(msg.payload.scales), s)
    np.testing.assert_array_equal(np.asarray(msg.payload.indices), i)
    # the pack the encoder runs reproduces the same bytes
    np.testing.assert_array_equal(
        np.asarray(R.pack_body(payload.values, payload.scales,
                               payload.indices)),
        np.frombuffer(expected_body, np.uint8))


@pytest.mark.parametrize("k", [1, 255, 256, 257, 4099])
def test_pack_body_jitted_matches_host_bytes(k):
    """The jitted wire pack (one device buffer) writes exactly the host
    bytes of values || scales || indices, for k on and off the 256-value
    scale groups."""
    rng = np.random.default_rng(k)
    q = rng.integers(-127, 128, k, dtype=np.int8)
    scales = rng.random(-(-k // 256), dtype=np.float32)
    idx = np.sort(rng.integers(0, 1 << 30, k)).astype(np.int32)
    body = np.asarray(jax.jit(R.pack_body)(q, scales, idx))
    assert body.dtype == np.uint8
    assert body.tobytes() == q.tobytes() + scales.tobytes() + idx.tobytes()
