"""Ring attention (sequence/context parallelism over the 'seq' mesh axis):
blockwise online-softmax attention with K/V rotated by lax.ppermute must
equal full attention (the long-context extension SURVEY §5 assigns to the
TPU rebuild)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.parallel import make_mesh, ring_attention
from paddle_tpu.ops.attention_ops import _attention_ref


def _full_ref(q, k, v, scale, causal):
    b, h, ln, dh = q.shape
    out = _attention_ref(q.reshape(b * h, ln, dh),
                         k.reshape(b * h, ln, dh),
                         v.reshape(b * h, ln, dh), scale, causal)
    return np.asarray(out).reshape(b, h, ln, dh)


@pytest.mark.parametrize("causal", [True, False])
def test_matches_full_attention(causal):
    rng = np.random.RandomState(0)
    b, h, ln, dh = 2, 4, 64, 16
    q = jnp.asarray(rng.randn(b, h, ln, dh).astype('float32'))
    k = jnp.asarray(rng.randn(b, h, ln, dh).astype('float32'))
    v = jnp.asarray(rng.randn(b, h, ln, dh).astype('float32'))
    mesh = make_mesh([('seq', 8)])
    out = ring_attention(q, k, v, mesh, causal=causal)
    ref = _full_ref(q, k, v, dh ** -0.5, causal)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-5)


def test_seq_longer_than_one_device_block():
    """The point of ring attention: every device sees only L/n rows yet
    the result equals global attention."""
    rng = np.random.RandomState(1)
    b, h, ln, dh = 1, 2, 128, 8
    q = jnp.asarray(rng.randn(b, h, ln, dh).astype('float32'))
    k = jnp.asarray(rng.randn(b, h, ln, dh).astype('float32'))
    v = jnp.asarray(rng.randn(b, h, ln, dh).astype('float32'))
    mesh = make_mesh([('seq', 8)])
    out = ring_attention(q, k, v, mesh, causal=True)
    ref = _full_ref(q, k, v, dh ** -0.5, True)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-5)


def test_divisibility_error():
    mesh = make_mesh([('seq', 8)])
    q = jnp.zeros((1, 1, 12, 4))
    with pytest.raises(ValueError, match="not divisible"):
        ring_attention(q, q, q, mesh)


def test_gradients_flow_through_ring():
    rng = np.random.RandomState(2)
    b, h, ln, dh = 1, 2, 32, 8
    q = jnp.asarray(rng.randn(b, h, ln, dh).astype('float32'))
    k = jnp.asarray(rng.randn(b, h, ln, dh).astype('float32'))
    v = jnp.asarray(rng.randn(b, h, ln, dh).astype('float32'))
    mesh = make_mesh([('seq', 4)])

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh, causal=True) ** 2)

    def loss_full(q, k, v):
        out = _attention_ref(q.reshape(b * h, ln, dh),
                             k.reshape(b * h, ln, dh),
                             v.reshape(b * h, ln, dh), dh ** -0.5, True)
        return jnp.sum(out ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for a, bb in zip(g_ring, g_full):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=3e-3, atol=3e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_zigzag_layout_matches(causal):
    """Zig-zag (balanced causal) layout: internally permuted sequence with
    true-position masking must still equal full attention."""
    rng = np.random.RandomState(3)
    b, h, ln, dh = 1, 2, 64, 8
    q = jnp.asarray(rng.randn(b, h, ln, dh).astype('float32'))
    k = jnp.asarray(rng.randn(b, h, ln, dh).astype('float32'))
    v = jnp.asarray(rng.randn(b, h, ln, dh).astype('float32'))
    mesh = make_mesh([('seq', 4)])
    out = ring_attention(q, k, v, mesh, causal=causal, zigzag=True)
    ref = _full_ref(q, k, v, dh ** -0.5, causal)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-5)


def test_ring_composes_with_dp_tp():
    """batch_axis/head_axis keep ring from all-gathering dp/tp shards."""
    rng = np.random.RandomState(4)
    b, h, ln, dh = 2, 2, 32, 8
    q = jnp.asarray(rng.randn(b, h, ln, dh).astype('float32'))
    k = jnp.asarray(rng.randn(b, h, ln, dh).astype('float32'))
    v = jnp.asarray(rng.randn(b, h, ln, dh).astype('float32'))
    mesh = make_mesh([('data', 2), ('model', 2), ('seq', 2)])
    out = ring_attention(q, k, v, mesh, causal=True,
                         batch_axis='data', head_axis='model')
    ref = _full_ref(q, k, v, dh ** -0.5, True)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-5)


def test_zigzag_permutation_properties():
    from paddle_tpu.parallel.ring_attention import zigzag_permutation
    perm, inv = zigzag_permutation(64, 4)
    assert sorted(perm.tolist()) == list(range(64))
    np.testing.assert_array_equal(perm[inv], np.arange(64))
    # shard d holds chunks d and 2n-1-d of the original sequence
    half = 64 // 8
    shard0 = perm[:16]
    assert set(shard0.tolist()) == set(range(0, 8)) | set(range(56, 64))
