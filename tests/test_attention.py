"""Fused attention kernel cross-checks (the reference jit-kernel testing
discipline, operators/jit/test.cc: every optimized impl vs the refer
impl over a shape sweep)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.ops.attention_ops import (flash_attention, _attention_ref)


@pytest.mark.parametrize("bh,ln,dh,causal", [
    (2, 16, 8, True),
    (2, 16, 8, False),
    (4, 64, 16, True),
    (1, 128, 32, True),
])
def test_pallas_kernel_matches_reference(bh, ln, dh, causal):
    """Kernel through the pallas interpreter == jnp reference."""
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(bh, ln, dh).astype('float32'))
    k = jnp.asarray(rng.randn(bh, ln, dh).astype('float32'))
    v = jnp.asarray(rng.randn(bh, ln, dh).astype('float32'))
    ref = _attention_ref(q, k, v, dh ** -0.5, causal)
    got = flash_attention(q, k, v, causal=causal, use_pallas='interpret')
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_gradients_flow():
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(2, 8, 4).astype('float32'))
    k = jnp.asarray(rng.randn(2, 8, 4).astype('float32'))
    v = jnp.asarray(rng.randn(2, 8, 4).astype('float32'))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, use_pallas=False) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_attention_ref(q, k, v, 0.5, True) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_lm_flash_matches_unfused():
    """The flagship LM with the fused attention path produces the same
    loss as the unfused softmax-matmul path."""
    from paddle_tpu.models.transformer import build_lm, LMConfig
    import paddle_tpu as fluid

    def run(use_flash):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 11
        cfg = LMConfig(vocab_size=128, seq_len=32, d_model=64, n_head=4,
                       n_layer=2, d_ff=128, dropout=0.0,
                       use_flash_attention=use_flash)
        with fluid.program_guard(main, startup):
            tokens, labels, logits, avg_loss = build_lm(cfg, is_test=True)
        exe = fluid.Executor()
        scope = fluid.Scope()
        rng = np.random.RandomState(0)
        feed = {'tokens': rng.randint(0, 128, (2, 32)).astype('int64'),
                'labels': rng.randint(0, 128, (2, 32)).astype('int64')}
        with fluid.scope_guard(scope):
            exe.run(startup, scope=scope)
            out, = exe.run(main, feed=feed, fetch_list=[avg_loss],
                           scope=scope)
        return float(np.asarray(out).reshape(()))

    np.testing.assert_allclose(run(True), run(False), rtol=1e-4)


def test_flash_attention_op_in_program():
    rng = np.random.RandomState(2)
    from test_detection_ops import _run_single_op
    q = rng.randn(2, 3, 8, 4).astype('float32')
    k = rng.randn(2, 3, 8, 4).astype('float32')
    v = rng.randn(2, 3, 8, 4).astype('float32')
    out, = _run_single_op(
        'flash_attention', {'Q': q, 'K': k, 'V': v}, {'Out': ['fa_out']},
        {'scale': 0.5, 'causal': True})
    ref = _attention_ref(
        jnp.asarray(q.reshape(6, 8, 4)), jnp.asarray(k.reshape(6, 8, 4)),
        jnp.asarray(v.reshape(6, 8, 4)), 0.5, True)
    np.testing.assert_allclose(out.reshape(6, 8, 4), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("bh,ln,dh,causal", [
    (2, 256, 32, True),      # 2 q-blocks x 2 k-blocks of 128
    (2, 256, 32, False),
    (1, 384, 16, True),      # 3x3 blocks
])
def test_blocked_kernel_matches_reference(bh, ln, dh, causal):
    """Multi-block grids (online softmax carries across k blocks)."""
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(bh, ln, dh).astype('float32'))
    k = jnp.asarray(rng.randn(bh, ln, dh).astype('float32'))
    v = jnp.asarray(rng.randn(bh, ln, dh).astype('float32'))
    ref = _attention_ref(q, k, v, dh ** -0.5, causal)
    got = flash_attention(q, k, v, causal=causal, use_pallas='interpret')
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_pallas_backward_matches_reference(causal):
    """dq/dk/dv pallas kernels (interpret) vs jnp AD of the reference —
    the flash backward is no longer a recompute fallback."""
    rng = np.random.RandomState(4)
    bh, ln, dh = 2, 256, 16
    q = jnp.asarray(rng.randn(bh, ln, dh).astype('float32'))
    k = jnp.asarray(rng.randn(bh, ln, dh).astype('float32'))
    v = jnp.asarray(rng.randn(bh, ln, dh).astype('float32'))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       use_pallas='interpret') ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_attention_ref(q, k, v, dh ** -0.5, causal) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-3, atol=3e-4)


def test_spmd_shard_map_kernel():
    """flash_attention_spmd under a (data, model) mesh: the kernel runs per
    shard via shard_map instead of falling back to einsum."""
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.ops.attention_ops import flash_attention_spmd
    rng = np.random.RandomState(5)
    b, h, ln, dh = 2, 4, 64, 16
    q = jnp.asarray(rng.randn(b, h, ln, dh).astype('float32'))
    k = jnp.asarray(rng.randn(b, h, ln, dh).astype('float32'))
    v = jnp.asarray(rng.randn(b, h, ln, dh).astype('float32'))
    mesh = make_mesh([('data', 2), ('model', 4)])
    out = flash_attention_spmd(q, k, v, mesh, causal=True,
                               use_pallas='interpret')
    ref = _attention_ref(q.reshape(b * h, ln, dh), k.reshape(b * h, ln, dh),
                         v.reshape(b * h, ln, dh), dh ** -0.5, True)
    np.testing.assert_allclose(np.asarray(out).reshape(b * h, ln, dh),
                               np.asarray(ref), rtol=2e-4, atol=2e-5)

    def loss(q, k, v):
        return jnp.sum(flash_attention_spmd(
            q, k, v, mesh, causal=True, use_pallas='interpret') ** 2)
    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    gref = jax.grad(lambda a, b_, c: jnp.sum(_attention_ref(
        a.reshape(8, ln, dh), b_.reshape(8, ln, dh), c.reshape(8, ln, dh),
        dh ** -0.5, True) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(grads, gref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=3e-3, atol=3e-4)


def test_spmd_seq_axis_dispatches_to_ring():
    """With a sharded sequence axis the op runs the ring-attention path —
    flash and ring are one op, not parallel universes."""
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.ops.attention_ops import flash_attention_spmd
    rng = np.random.RandomState(6)
    b, h, ln, dh = 2, 2, 64, 8
    q = jnp.asarray(rng.randn(b, h, ln, dh).astype('float32'))
    k = jnp.asarray(rng.randn(b, h, ln, dh).astype('float32'))
    v = jnp.asarray(rng.randn(b, h, ln, dh).astype('float32'))
    mesh = make_mesh([('data', 2), ('model', 2), ('seq', 2)])
    out = flash_attention_spmd(q, k, v, mesh, causal=True)
    ref = _attention_ref(q.reshape(b * h, ln, dh), k.reshape(b * h, ln, dh),
                         v.reshape(b * h, ln, dh), dh ** -0.5, True)
    np.testing.assert_allclose(np.asarray(out).reshape(b * h, ln, dh),
                               np.asarray(ref), rtol=2e-4, atol=2e-5)


def test_flash_attention_layer():
    """layers.flash_attention wrapper == the op == the jnp reference."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        q = fluid.layers.data(name='fq', shape=[2, 16, 8], dtype='float32')
        k = fluid.layers.data(name='fk', shape=[2, 16, 8], dtype='float32')
        v = fluid.layers.data(name='fv', shape=[2, 16, 8], dtype='float32')
        out = fluid.layers.flash_attention(q, k, v, causal=True)
    exe = fluid.Executor()
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    Q = rng.randn(2, 2, 16, 8).astype('float32')
    K = rng.randn(2, 2, 16, 8).astype('float32')
    V = rng.randn(2, 2, 16, 8).astype('float32')
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        got, = exe.run(main, feed={'fq': Q, 'fk': K, 'fv': V},
                       fetch_list=[out], scope=scope)
    ref = _attention_ref(jnp.asarray(Q.reshape(4, 16, 8)),
                         jnp.asarray(K.reshape(4, 16, 8)),
                         jnp.asarray(V.reshape(4, 16, 8)), 8 ** -0.5, True)
    np.testing.assert_allclose(np.asarray(got).reshape(4, 16, 8),
                               np.asarray(ref), rtol=1e-4, atol=1e-5)


def test_spmd_seq_axis_ring_zigzag_attr():
    """ring_zigzag attr: balanced causal ring layout through the op
    surface matches single-device logits (VERDICT r2 #8 'done')."""
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.ops.attention_ops import flash_attention_spmd
    rng = np.random.RandomState(8)
    b, h, ln, dh = 2, 2, 64, 8
    q = jnp.asarray(rng.randn(b, h, ln, dh).astype('float32'))
    k = jnp.asarray(rng.randn(b, h, ln, dh).astype('float32'))
    v = jnp.asarray(rng.randn(b, h, ln, dh).astype('float32'))
    mesh = make_mesh([('data', 2), ('seq', 4)])
    out = flash_attention_spmd(q, k, v, mesh, causal=True,
                               ring_zigzag=True)
    ref = _attention_ref(q.reshape(b * h, ln, dh),
                         k.reshape(b * h, ln, dh),
                         v.reshape(b * h, ln, dh), dh ** -0.5, True)
    np.testing.assert_allclose(np.asarray(out).reshape(b * h, ln, dh),
                               np.asarray(ref), rtol=2e-4, atol=2e-5)


def test_masked_flash_kernel_matches_reference():
    """Per-key padding bias fused into the kernels (fwd + bwd), the BERT
    encoder path: interpret-mode kernels vs the biased jnp reference."""
    from paddle_tpu.ops.attention_ops import _attention_ref_biased
    rng = np.random.RandomState(9)
    B, H, L, dh = 2, 2, 256, 16
    q = jnp.asarray(rng.randn(B, H, L, dh).astype('float32'))
    k = jnp.asarray(rng.randn(B, H, L, dh).astype('float32'))
    v = jnp.asarray(rng.randn(B, H, L, dh).astype('float32'))
    bias_np = np.zeros((B, L), 'float32')
    bias_np[0, -40:] = -1e9
    bias_np[1, -7:] = -1e9
    bias = jnp.asarray(bias_np)
    for causal in (False, True):
        ref = _attention_ref_biased(
            q.reshape(B * H, L, dh), k.reshape(B * H, L, dh),
            v.reshape(B * H, L, dh), bias, dh ** -0.5, causal, H)
        got = flash_attention(q, k, v, causal=causal,
                              use_pallas='interpret',
                              key_padding_bias=bias)
        np.testing.assert_allclose(
            np.asarray(got).reshape(B * H, L, dh), np.asarray(ref),
            rtol=2e-4, atol=2e-5)
        g1 = jax.grad(lambda a: jnp.sum(flash_attention(
            a, k, v, causal=causal, use_pallas='interpret',
            key_padding_bias=bias) ** 2))(q)
        g2 = jax.grad(lambda a: jnp.sum(_attention_ref_biased(
            a.reshape(B * H, L, dh), k.reshape(B * H, L, dh),
            v.reshape(B * H, L, dh), bias, dh ** -0.5, causal,
            H).reshape(B, H, L, dh) ** 2))(q)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=3e-3, atol=3e-4)


def test_masked_flash_bwd_all_padded_row_bounded():
    """ADVICE r3: a batch element whose keys are ALL padded (bias -1e9
    everywhere) must produce zero grads through the pallas backward, not
    exp(-lse) ~ e^69 garbage."""
    rng = np.random.RandomState(5)
    B, H, L, dh = 2, 1, 128, 16
    q = jnp.asarray(rng.randn(B, H, L, dh).astype('float32'))
    k = jnp.asarray(rng.randn(B, H, L, dh).astype('float32'))
    v = jnp.asarray(rng.randn(B, H, L, dh).astype('float32'))
    bias_np = np.zeros((B, L), 'float32')
    bias_np[0, :] = -1e9                       # batch 0 entirely padded
    bias = jnp.asarray(bias_np)
    gq, gk, gv = jax.grad(
        lambda a, b, c: jnp.sum(flash_attention(
            a, b, c, causal=False, use_pallas='interpret',
            key_padding_bias=bias) ** 2), argnums=(0, 1, 2))(q, k, v)
    for g in (gq, gk, gv):
        arr = np.asarray(g)
        assert np.isfinite(arr).all()
        assert np.abs(arr[0]).max() == 0.0     # padded element: exact zero
        assert np.abs(arr).max() < 1e3


def test_bert_flash_vs_unfused_parity():
    """BERT with the masked flash path == the unfused mask_var path."""
    from paddle_tpu.models.bert import (BertConfig, build_bert_pretrain,
                                        make_pretrain_batch)

    def run(flash):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 17
        cfg = BertConfig(vocab_size=64, seq_len=16, d_model=16, n_head=2,
                         n_layer=1, d_ff=32, dropout=0.0,
                         max_predictions=2, use_flash_attention=flash)
        with fluid.program_guard(main, startup):
            total, mlm, nsp = build_bert_pretrain(cfg, is_test=True)
        exe = fluid.Executor()
        scope = fluid.Scope()
        rng = np.random.RandomState(3)
        feed = make_pretrain_batch(cfg, 4, rng)
        feed['input_mask'][:, -5:] = 0.0
        with fluid.scope_guard(scope):
            exe.run(startup, scope=scope)
            out, = exe.run(main, feed=feed, fetch_list=[total],
                           scope=scope)
        return float(np.asarray(out).reshape(()))

    np.testing.assert_allclose(run(True), run(False), rtol=1e-4)


def test_spmd_masked_flash_kernel():
    """Biased (padding-mask) flash under a (data, model) mesh runs the
    kernel per shard with the bias sharded along data."""
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.ops.attention_ops import (flash_attention_spmd,
                                              _attention_ref_biased)
    rng = np.random.RandomState(11)
    B, H, L, dh = 4, 2, 64, 8
    q = jnp.asarray(rng.randn(B, H, L, dh).astype('float32'))
    k = jnp.asarray(rng.randn(B, H, L, dh).astype('float32'))
    v = jnp.asarray(rng.randn(B, H, L, dh).astype('float32'))
    bias_np = np.zeros((B, L), 'float32')
    bias_np[:, -9:] = -1e9
    bias = jnp.asarray(bias_np)
    mesh = make_mesh([('data', 4), ('model', 2)])
    out = flash_attention_spmd(q, k, v, mesh, causal=False,
                               use_pallas='interpret',
                               key_padding_bias=bias)
    ref = _attention_ref_biased(
        q.reshape(B * H, L, dh), k.reshape(B * H, L, dh),
        v.reshape(B * H, L, dh), bias, dh ** -0.5, False, H)
    np.testing.assert_allclose(np.asarray(out).reshape(B * H, L, dh),
                               np.asarray(ref), rtol=2e-4, atol=2e-5)


def test_unfused_fallback_honors_padding_bias():
    """multi_head_attention's unfused branch must apply key_padding_bias
    (round-3 review finding): flash vs unfused parity with pads."""
    from paddle_tpu.models.bert import (BertConfig, build_bert_pretrain,
                                        make_pretrain_batch)

    def run(flash, drop):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 19
        cfg = BertConfig(vocab_size=64, seq_len=16, d_model=16, n_head=2,
                         n_layer=1, d_ff=32, dropout=0.0,
                         attn_dropout=drop, max_predictions=2,
                         use_flash_attention=flash)
        with fluid.program_guard(main, startup):
            total, mlm, nsp = build_bert_pretrain(cfg, is_test=True)
        exe = fluid.Executor()
        scope = fluid.Scope()
        rng = np.random.RandomState(3)
        feed = make_pretrain_batch(cfg, 4, rng)
        feed['input_mask'][:, -5:] = 0.0
        with fluid.scope_guard(scope):
            exe.run(startup, scope=scope)
            out, = exe.run(main, feed=feed, fetch_list=[total],
                           scope=scope)
        return float(np.asarray(out).reshape(()))

    # attn_dropout forces the UNFUSED path even with flash on; is_test
    # disables the dropout itself, so all three must agree
    a = run(True, 0.0)       # fused masked kernel
    b = run(False, 0.0)      # mask_var path
    c = run(True, 0.5)       # unfused path w/ key_padding_bias branch
    np.testing.assert_allclose(a, b, rtol=1e-4)
    np.testing.assert_allclose(a, c, rtol=1e-4)


# ---------------------------------------------------------------------------
# ISSUE 52: the tile schedule. A grid step walks sub-tiles inside its block
# with a trip count that ends (dKV: starts) at the diagonal; the tile sizes
# are `flash_attention_tiling`'s.

def _walk_operands(ln, dh, dtype, bias_kind, seed=7):
    """Two batches of one head: q, k, v, dO, and a [2, L] padding bias
    ('tail': the last keys dropped; 'row': batch 0 dropped whole)."""
    rng = np.random.RandomState(seed)
    q, k, v, do = (jnp.asarray(rng.randn(2, ln, dh), dtype)
                   for _ in range(4))
    bias = None
    if bias_kind:
        b = np.zeros((2, ln), 'float32')
        b[1, -37:] = -1e9
        b[0, -5:] = -1e9
        if bias_kind == 'row':
            b[0, :] = -1e9
        bias = jnp.asarray(b)
    return q, k, v, do, bias


# (L, dh, dtype, causal, bias, (bq, bk, block) forward and dQ, the same dKV)
_WALKS = [
    # two and three sub-tiles of 128 in one block, the last on the diagonal
    (256, 64, 'float32', True, None, (128, 128, 256), (128, 128, 256)),
    (384, 128, 'float32', True, None, (128, 128, 384), (128, 128, 384)),
    (256, 64, 'bfloat16', True, None, (128, 128, 256), (128, 128, 256)),
    (384, 128, 'bfloat16', True, None, (128, 128, 384), (128, 128, 384)),
    # a block a sub-tile: the grid's third axis walks, the index map clamps
    (384, 64, 'float32', True, None, (128, 128, 128), (128, 128, 128)),
    (512, 64, 'float32', True, None, (128, 128, 256), (128, 128, 256)),
    # bq != bk: the diagonal crosses two sub-tiles of a step
    (512, 64, 'float32', True, None, (256, 128, 512), (256, 128, 512)),
    (512, 64, 'float32', True, None, (128, 256, 512), (128, 256, 512)),
    (512, 128, 'bfloat16', True, None, (256, 128, 256), (128, 256, 256)),
    # no mask: every sub-tile takes the unmasked trip
    (256, 64, 'float32', False, None, (128, 128, 256), (128, 128, 256)),
    (384, 64, 'bfloat16', False, None, (128, 128, 128), (128, 128, 128)),
    # key_padding_bias, a sub-tile a row of the bias block
    (384, 64, 'float32', False, 'tail', (128, 128, 384), (128, 128, 384)),
    (256, 64, 'float32', True, 'tail', (128, 128, 128), (128, 128, 128)),
    (256, 128, 'bfloat16', False, 'tail', (128, 128, 256), (128, 128, 256)),
    (512, 64, 'float32', True, 'tail', (256, 128, 512), (128, 256, 512)),
    # the rule's own answer: one tile
    (256, 64, 'float32', True, None, None, None),
]


@pytest.mark.parametrize("ln,dh,dtype,causal,bias_kind,tiling,tiling_dkv",
                         _WALKS)
def test_walked_kernels_match_reference(ln, dh, dtype, causal, bias_kind,
                                        tiling, tiling_dkv):
    """Forward and both gradients through the interpreter against the
    jnp reference, at the tolerances of the tests above (float32); the
    reference of a bfloat16 case is the float32 one of the same values."""
    from paddle_tpu.ops import attention_ops as A
    q, k, v, do, bias = _walk_operands(ln, dh, dtype, bias_kind)
    scale = dh ** -0.5
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    if bias is None:
        ref = lambda a, b, c: A._attention_ref(a, b, c, scale, causal)
    else:
        ref = lambda a, b, c: A._attention_ref_biased(a, b, c, bias, scale,
                                                      causal, 1)
    o_ref, vjp = jax.vjp(ref, *f32)
    g_ref = vjp(do.astype(jnp.float32))
    o, lse = A._flash_fwd_pallas(q, k, v, scale, causal, True, bias=bias,
                                 tiling=tiling)
    grads = A._flash_bwd_pallas(q, k, v, o, lse, do, scale, causal, True,
                                bias=bias, tiling_dq=tiling,
                                tiling_dkv=tiling_dkv)
    fwd_tol, bwd_tol = (dict(rtol=2e-4, atol=2e-5), dict(rtol=3e-3,
                                                         atol=3e-4)) \
        if dtype == 'float32' else (dict(rtol=2e-2, atol=2e-2),) * 2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref), **fwd_tol)
    for got, want in zip(grads, g_ref):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want), **bwd_tol)


@pytest.mark.parametrize("causal,tiling", [
    (False, (128, 128, 256)), (True, (128, 128, 128))])
def test_walked_kernels_all_padded_row(causal, tiling):
    """A batch whose keys are ALL padded: the walked backward gives exact
    zeros there and the other batch its reference gradients."""
    from paddle_tpu.ops import attention_ops as A
    q, k, v, do, bias = _walk_operands(256, 64, 'float32', 'row')
    scale = 64 ** -0.5
    o, lse = A._flash_fwd_pallas(q, k, v, scale, causal, True, bias=bias,
                                 tiling=tiling)
    grads = A._flash_bwd_pallas(q, k, v, o, lse, do, scale, causal, True,
                                bias=bias, tiling_dq=tiling,
                                tiling_dkv=tiling)
    _, vjp = jax.vjp(lambda a, b, c: A._attention_ref_biased(
        a[1:], b[1:], c[1:], bias[1:], scale, causal, 1), q, k, v)
    for got, want in zip(grads, vjp(do[1:])):
        got = np.asarray(got)
        assert np.isfinite(got).all()
        assert np.abs(got[0]).max() == 0.0
        np.testing.assert_allclose(got[1], np.asarray(want)[1], rtol=3e-3,
                                   atol=3e-4)


@pytest.mark.parametrize("kernel", ['fwd', 'bwd_dq', 'bwd_dkv'])
@pytest.mark.parametrize("dtype", ['bfloat16', 'float32'])
@pytest.mark.parametrize("ln,dh", [(2048, 64), (2048, 128), (8192, 128),
                                   (512, 64), (128, 64), (100, 64),
                                   (16, 8)])
def test_flash_tiling_rule(ln, dh, dtype, kernel, monkeypatch):
    """The tile function alone, at the cells' shapes and the short ones:
    the sizes divide L, the walked blocks fit the stated VMEM budget, one
    tile where L is short or odd, and the environment changes nothing."""
    from paddle_tpu.ops import attention_ops as A
    bq, bk, block = A.flash_attention_tiling(ln, dh, dtype, kernel)
    walk = bq if kernel == 'bwd_dkv' else bk
    assert ln % bq == 0 and ln % bk == 0 and ln % block == 0
    assert block % walk == 0
    lanes = -(-dh // 128) * 128
    held = 2 * 2 * block * lanes * jnp.dtype(dtype).itemsize
    assert held <= A._WALK_VMEM_BYTES or block == walk
    if ln <= 512 or ln % 128:
        assert (bq, bk, block) == (ln, ln, ln)
    if ln == 2048:
        assert (bq, bk, block) == (512, 512, 2048)    # the sweep's, PR 52
    if ln == 8192 and dtype == 'float32':
        assert block < ln                   # 16 MB of K and V: not one block
    monkeypatch.setenv('PADDLE_FLASH_BQ', '128')
    monkeypatch.setenv('PADDLE_FLASH_BK', '256')
    assert A.flash_attention_tiling(ln, dh, dtype, kernel) == (bq, bk, block)
    assert A.flash_shapes_ok(ln)


def test_flash_tile_knobs_are_gone():
    """ROADMAP D6: no file of the package reads PADDLE_FLASH_BQ / _BK."""
    import pathlib
    import paddle_tpu
    root = pathlib.Path(paddle_tpu.__file__).parent
    hits = [str(p) for p in root.rglob('*.py')
            if 'PADDLE_FLASH' in p.read_text()]
    assert hits == []


def test_flash_tiling_counter():
    """`flash_attention_tiling_total{kernel,bq,bk}`: + 1 a kernel a
    lowering -- the forward alone lowers one kernel, its gradient three
    (the forward again, dQ, dKV) -- and nothing at run time."""
    from paddle_tpu import monitor
    q, k, v, _, _ = _walk_operands(256, 16, 'float32', None)

    def series(before):
        return {key: val for key, val in
                monitor.counter_delta(before).items()
                if key.startswith('flash_attention_tiling_total')}

    def key(kernel):
        return 'flash_attention_tiling_total{bk=256,bq=256,kernel=%s}' \
            % kernel
    fwd = jax.jit(lambda a: flash_attention(a, k, v, use_pallas='interpret'))
    before = monitor.counters()
    fwd.lower(q)
    assert series(before) == {key('fwd'): 1.0}
    grad = jax.jit(jax.grad(lambda a: jnp.sum(flash_attention(
        a, k, v, use_pallas='interpret'))))
    before = monitor.counters()
    compiled = grad.lower(q).compile()
    assert series(before) == {key('fwd'): 1.0, key('bwd_dq'): 1.0,
                              key('bwd_dkv'): 1.0}
    before = monitor.counters()
    compiled(q)
    assert series(before) == {}


# ---------------------------------------------------------------------------
# Mosaic, without a chip: the three kernels at the cells' widths against a
# described v5e (block shapes, the dynamic trip counts, the [dh, bq]
# accumulator's transpose, scoped VMEM). The topology is described inside
# a fixture, never at import (one process at a time may load libtpu: under
# xdist only this file's worker does).

@pytest.fixture(scope='module')
def one_chip():
    import os
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    for key, val in (('TPU_ACCELERATOR_TYPE', 'v5litepod-4'),
                     ('TPU_WORKER_HOSTNAMES', 'localhost'),
                     ('TPU_SKIP_MDS_QUERY', '1')):
        os.environ.setdefault(key, val)
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # noqa: BLE001 — no libtpu here: nothing to ask
        pytest.skip('no v5e:2x2 topology can be described here: %s' % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("bh,ln,dh,dtype,causal,heads", [
    (64, 2048, 64, 'bfloat16', True, 0),     # fd355m-train-2k
    (32, 2048, 64, 'bfloat16', True, 0),     # fd1.3b-train-4chip, a chip
    (64, 2048, 64, 'float32', True, 0),      # the train driver's eval forward
    (32, 2048, 128, 'bfloat16', True, 0),    # the newer configurations' heads
    (8, 8192, 128, 'float32', True, 0),      # K and V in four blocks
    (48, 512, 64, 'bfloat16', False, 12),    # BERT: one tile, padding bias
    (48, 128, 64, 'float32', False, 12),
])
def test_mosaic_accepts_the_kernels_at_the_cells_shapes(one_chip, bh, ln, dh,
                                                        dtype, causal,
                                                        heads):
    from paddle_tpu.ops import attention_ops as A

    def sds(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)
    scale = dh ** -0.5
    x, row = sds((bh, ln, dh)), sds((bh, ln), 'float32')
    bias = [sds((bh // heads, ln), 'float32')] if heads else []

    def fwd(q, k, v, *b):
        return A._flash_fwd_pallas(q, k, v, scale, causal, False,
                                   bias=b[0] if b else None,
                                   n_heads=heads or 1)

    def bwd(q, k, v, o, lse, do, *b):
        return A._flash_bwd_pallas(q, k, v, o, lse, do, scale, causal,
                                   False, bias=b[0] if b else None,
                                   n_heads=heads or 1)
    text = jax.jit(fwd).lower(x, x, x, *bias).compile().as_text()
    assert text.count('tpu_custom_call') >= 1
    text = jax.jit(bwd).lower(x, x, x, x, row, x, *bias).compile().as_text()
    assert text.count('tpu_custom_call') >= 2


def test_kernbench_flash_attention_case(capsys):
    """tools/kernbench.py's `flash_attention` case at its toy shape through
    the interpreter: the rule's column and a stated tiling, the three
    kernels and the whole, and the JSON line the CLI prints."""
    import json
    import sys
    from tools import kernbench
    argv = sys.argv
    sys.argv = ['kernbench.py', '--cases', 'flash_attention', '--size',
                'small', '--rounds', '1', '--k', '1', '--tilings', '128,128']
    try:
        kernbench.main()
    finally:
        sys.argv = argv
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    (shape, row), = res['flash_attention'].items()
    assert shape == 'toy [2, 256, 64] float32'
    assert set(row) == {'rule: 256,256 / 256,256 / 256,256', '128,128'}
    for col in row.values():
        assert set(col) == {'fwd', 'bwd_dq', 'bwd_dkv', 'vjp'}
        for kern in col.values():
            assert kern['ms'] > 0 and 'error' not in kern
