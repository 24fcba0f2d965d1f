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


def test_lm_flash_hands_the_product_over(monkeypatch):
    """ISSUE 57: a flash `build_lm` program has NO transpose2 / slice /
    squeeze2 / reshape2 op between `attn.qkv`'s fc and `attn.proj`'s -- the
    op takes the fused product as it is -- and, with heads of 64 through
    the interpreter (the packed kernels, by the layout counter), its loss
    and every parameter gradient match the unfused build's."""
    from paddle_tpu import monitor
    from paddle_tpu.models.transformer import build_lm, LMConfig
    monkeypatch.setenv('PADDLE_FUSED_TIER', 'interpret')

    def run(use_flash):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 11
        cfg = LMConfig(vocab_size=64, seq_len=32, d_model=128, n_head=2,
                       n_layer=2, d_ff=128, dropout=0.0,
                       use_flash_attention=use_flash)
        with fluid.program_guard(main, startup):
            _, _, _, avg_loss = build_lm(cfg, is_test=False)
            pairs = fluid.append_backward(avg_loss)
        types = [op.type for op in main.global_block().ops]
        exe = fluid.Executor()
        scope = fluid.Scope()
        rng = np.random.RandomState(0)
        feed = {'tokens': rng.randint(0, 64, (2, 32)).astype('int64'),
                'labels': rng.randint(0, 64, (2, 32)).astype('int64')}
        with fluid.scope_guard(scope):
            exe.run(startup, scope=scope)
            out = exe.run(main, feed=feed, scope=scope,
                          fetch_list=[avg_loss] + [g for _, g in pairs])
        return types, [p.name for p, _ in pairs], [np.asarray(x)
                                                   for x in out]

    before = monitor.counters()
    types, names, flash = run(True)
    layouts = {key: val for key, val in monitor.counter_delta(before).items()
               if key.startswith('flash_attention_layout_total')}
    # forward, and the forward again with dQ and dKV under the gradient,
    # once for the two layers (the calls are jitted)
    assert set(layouts) == {'flash_attention_layout_total{layout=packed}'}
    at = [i for i, t in enumerate(types) if t == 'flash_attention']
    assert len(at) == 2
    for i in at:
        # fc = mul + elementwise_add on either side, nothing between
        assert types[i - 2:i] == ['mul', 'elementwise_add'], types[i - 4:i]
        assert types[i + 1] == 'mul', types[i:i + 3]
    assert not {'transpose2', 'slice', 'squeeze2'} & set(types[:max(at)])
    unfused_types, unfused_names, unfused = run(False)
    assert 'transpose2' in unfused_types and names == unfused_names
    np.testing.assert_allclose(flash[0], unfused[0], rtol=1e-4)
    for name, got, want in zip(names, flash[1:], unfused[1:]):
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-5,
                                   err_msg=name)


def _run_fused_form(qkv, heads, causal=True):
    """The `flash_attention` op's fused input form alone: Out of QKV."""
    from test_detection_ops import _run_single_op
    out, = _run_single_op(
        'flash_attention', {'QKV': qkv}, {'Out': ['fa_packed_out']},
        {'num_heads': heads, 'causal': causal})
    return np.asarray(out)


@pytest.mark.parametrize("heads,dh,ln,axes,layout", [
    (2, 64, 128, None, 'packed'),                # two heads a lane block
    (1, 128, 128, None, 'packed'),               # one
    (4, 96, 128, None, 'heads'),                 # no whole lane blocks
    (3, 64, 128, None, 'heads'),                 # 192 columns: one and a half
    # (a length a case: the executor keeps a program it has compiled)
    (2, 64, 64, [('data', 2)], 'packed'),        # shard_mapped over the batch
    (2, 64, 96, [('data', 2), ('model', 2)], 'heads'),   # heads are sharded
    (2, 64, 32, [('data', 2), ('seq', 2)], None),    # ring attention: no kernel
])
def test_fused_form_layout_is_chosen_from_shapes_and_mesh(
        monkeypatch, heads, dh, ln, axes, layout):
    """ISSUE 57: which operand layout the op's fused form lowers to, read
    from `flash_attention_layout_total`, and that each gives the
    reference's context."""
    from paddle_tpu import monitor
    from paddle_tpu.ops import attention_ops as A
    from paddle_tpu.parallel import api as papi, make_mesh
    monkeypatch.setenv('PADDLE_FUSED_TIER', 'interpret')
    if axes:
        monkeypatch.setattr(papi, '_ACTIVE_MESH', make_mesh(axes))
    rng = np.random.RandomState(13)
    qkv = rng.randn(2, ln, 3 * heads * dh).astype('float32')
    before = monitor.counters()
    got = _run_fused_form(qkv, heads)
    series = {key: val for key, val in monitor.counter_delta(before).items()
              if key.startswith('flash_attention_layout_total')}
    assert series == ({'flash_attention_layout_total{layout=%s}' % layout:
                       1.0} if layout else {})
    ref = A._attention_ref(*_head_major(jnp.asarray(qkv), heads, 3),
                           dh ** -0.5, True)
    np.testing.assert_allclose(got, np.asarray(_packed(ref, heads)),
                               rtol=2e-4, atol=2e-5)


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


def test_spmd_packed_kernel_over_the_batch():
    """ISSUE 57: under a 'data' mesh the packed call is shard_mapped over
    the batch (`P(data, None, None)`): the context and the product's one
    cotangent against the reference's, turned head-major."""
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.ops.attention_ops import flash_attention_packed
    rng = np.random.RandomState(5)
    b, h, ln, dh = 4, 2, 128, 64
    qkv = jnp.asarray(rng.randn(b, ln, 3 * h * dh).astype('float32'))
    mesh = make_mesh([('data', 4)])
    out = flash_attention_packed(qkv, h, mesh=mesh, interpret=True)

    def ref(x):
        return _packed(_attention_ref(*_head_major(x, h, 3), dh ** -0.5,
                                      True), h)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(qkv)),
                               rtol=2e-4, atol=2e-5)
    grad = jax.grad(lambda x: jnp.sum(flash_attention_packed(
        x, h, mesh=mesh, interpret=True) ** 2))(qkv)
    want = jax.grad(lambda x: jnp.sum(ref(x) ** 2))(qkv)
    assert grad.shape == qkv.shape
    np.testing.assert_allclose(np.asarray(grad), np.asarray(want),
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


# (L, dh, dtype, causal, bias, (bq, bk, block) forward and dQ, the same dKV,
#  heads of the packed product -- 0: head-major operands)
_WALKS = [
    # two and three sub-tiles of 128 in one block, the last on the diagonal
    (256, 64, 'float32', True, None, (128, 128, 256), (128, 128, 256), 0),
    (384, 128, 'float32', True, None, (128, 128, 384), (128, 128, 384), 0),
    (256, 64, 'bfloat16', True, None, (128, 128, 256), (128, 128, 256), 0),
    (384, 128, 'bfloat16', True, None, (128, 128, 384), (128, 128, 384), 0),
    # a block a sub-tile: the grid's third axis walks, the index map clamps
    (384, 64, 'float32', True, None, (128, 128, 128), (128, 128, 128), 0),
    (512, 64, 'float32', True, None, (128, 128, 256), (128, 128, 256), 0),
    # bq != bk: the diagonal crosses two sub-tiles of a step
    (512, 64, 'float32', True, None, (256, 128, 512), (256, 128, 512), 0),
    (512, 64, 'float32', True, None, (128, 256, 512), (128, 256, 512), 0),
    (512, 128, 'bfloat16', True, None, (256, 128, 256), (128, 256, 256), 0),
    # no mask: every sub-tile takes the unmasked trip
    (256, 64, 'float32', False, None, (128, 128, 256), (128, 128, 256), 0),
    (384, 64, 'bfloat16', False, None, (128, 128, 128), (128, 128, 128), 0),
    # key_padding_bias, a sub-tile a row of the bias block
    (384, 64, 'float32', False, 'tail', (128, 128, 384), (128, 128, 384), 0),
    (256, 64, 'float32', True, 'tail', (128, 128, 128), (128, 128, 128), 0),
    (256, 128, 'bfloat16', False, 'tail', (128, 128, 256), (128, 128, 256),
     0),
    (512, 64, 'float32', True, 'tail', (256, 128, 512), (128, 256, 512), 0),
    # the rule's own answer: one tile
    (256, 64, 'float32', True, None, None, None, 0),
    # ISSUE 57, the packed product [2, L, 3 x H x dh]: two heads of 64 a
    # lane block (H 4: two blocks, so Q, K and V start at column blocks 0,
    # 2 and 4) and one head of 128 (H 2); one tile under the rule ...
    (256, 64, 'float32', True, None, None, None, 4),
    (256, 64, 'bfloat16', False, None, None, None, 4),
    (256, 128, 'float32', False, None, None, None, 2),
    (256, 128, 'bfloat16', True, None, None, None, 2),
    # ... and L 1024 at a sweep tiling of 256: four tiles, two trips a
    # block of 512 and two blocks, or one block of four trips
    (1024, 64, 'float32', True, None, (256, 256, 512), (256, 256, 512), 4),
    (1024, 64, 'bfloat16', True, None, (256, 256, 1024), (256, 256, 1024),
     4),
    (1024, 64, 'float32', False, None, (256, 256, 256), (256, 256, 256), 4),
    (1024, 128, 'float32', True, None, (256, 256, 512), (256, 256, 512), 2),
    (1024, 128, 'bfloat16', False, None, (256, 256, 1024), (256, 256, 512),
     2),
]


def _head_major(x, heads, n=1):
    """[B, L, n x H x dh] -> n of [B x H, L, dh] (`_packed` turns one
    back)."""
    b, ln, w = x.shape
    dh = w // (n * heads)
    x = x.reshape(b, ln, n, heads, dh).transpose(2, 0, 3, 1, 4)
    return tuple(x.reshape(n, b * heads, ln, dh))


def _packed(x, heads):
    """[B x H, L, dh] -> [B, L, H x dh]."""
    bh, ln, dh = x.shape
    return x.reshape(bh // heads, heads, ln, dh).transpose(
        0, 2, 1, 3).reshape(bh // heads, ln, heads * dh)


@pytest.mark.parametrize(
    "ln,dh,dtype,causal,bias_kind,tiling,tiling_dkv,heads", _WALKS)
def test_walked_kernels_match_reference(ln, dh, dtype, causal, bias_kind,
                                        tiling, tiling_dkv, heads):
    """Forward and both gradients through the interpreter against the
    jnp reference, at the tolerances of the tests above (float32); the
    reference of a bfloat16 case is the float32 one of the same values.
    With `heads` the kernels read the packed product and the reference
    the same values turned head-major."""
    from paddle_tpu.ops import attention_ops as A
    scale = dh ** -0.5
    if heads:
        rng = np.random.RandomState(7)
        qkv = jnp.asarray(rng.randn(2, ln, 3 * heads * dh), dtype)
        do = jnp.asarray(rng.randn(2, ln, heads * dh), dtype)
        f32 = _head_major(qkv.astype(jnp.float32), heads, 3)
        do_ref, = _head_major(do.astype(jnp.float32), heads)
        bias = None
    else:
        q, k, v, do, bias = _walk_operands(ln, dh, dtype, bias_kind)
        f32 = [x.astype(jnp.float32) for x in (q, k, v)]
        do_ref = do.astype(jnp.float32)
    if bias is None:
        ref = lambda a, b, c: A._attention_ref(a, b, c, scale, causal)
    else:
        ref = lambda a, b, c: A._attention_ref_biased(a, b, c, bias, scale,
                                                      causal, 1)
    o_ref, vjp = jax.vjp(ref, *f32)
    g_ref = vjp(do_ref)
    if heads:
        o, lse = A._flash_fwd_pallas(qkv, None, None, scale, causal, True,
                                     tiling=tiling, heads=heads)
        assert o.shape == do.shape and lse.shape == (2, heads, ln)
        grads = A._flash_bwd_pallas(qkv, None, None, o, lse, do, scale,
                                    causal, True, tiling_dq=tiling,
                                    tiling_dkv=tiling_dkv, heads=heads)
        o_ref = _packed(o_ref, heads)
        g_ref = [_packed(g, heads) for g in g_ref]
    else:
        o, lse = A._flash_fwd_pallas(q, k, v, scale, causal, True,
                                     bias=bias, tiling=tiling)
        grads = A._flash_bwd_pallas(q, k, v, o, lse, do, scale, causal,
                                    True, bias=bias, tiling_dq=tiling,
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
    if dh == 64:
        # two heads of 64 a lane block fill the 128 lanes one padded head
        # takes: the same rows are held
        assert A.flash_attention_tiling(ln, dh, dtype, kernel, heads=2) \
            == (bq, bk, block)
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


def test_flash_layout_counter():
    """`flash_attention_layout_total{layout}`: + 1 a kernel a lowering
    beside the tiling's counter -- 'heads' for head-major operands,
    'packed' for the fused product -- and nothing at run time."""
    from paddle_tpu import monitor
    from paddle_tpu.ops.attention_ops import flash_attention_packed
    q, k, v, _, _ = _walk_operands(256, 16, 'float32', None)
    qkv = jnp.zeros((2, 256, 3 * 2 * 64), jnp.float32)

    def series(before):
        return {key: val for key, val in
                monitor.counter_delta(before).items()
                if key.startswith('flash_attention_layout_total')}
    for layout, fn, x in (
            ('heads', lambda a: jnp.sum(flash_attention(
                a, k, v, use_pallas='interpret')), q),
            ('packed', lambda a: jnp.sum(flash_attention_packed(
                a, 2, interpret=True)), qkv)):
        key = 'flash_attention_layout_total{layout=%s}' % layout
        before = monitor.counters()
        jax.jit(fn).lower(x)
        assert series(before) == {key: 1.0}
        before = monitor.counters()
        compiled = jax.jit(jax.grad(fn)).lower(x).compile()
        assert series(before) == {key: 3.0}     # forward, dQ, dKV
        before = monitor.counters()
        compiled(x)
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


@pytest.mark.parametrize("bh,ln,dh,dtype,causal,heads,packed", [
    (64, 2048, 64, 'bfloat16', True, 0, 0),  # fd355m-train-2k before PR 57
    (32, 2048, 64, 'bfloat16', True, 0, 0),  # fd1.3b-train-4chip, a chip
    (64, 2048, 64, 'float32', True, 0, 0),   # the train driver's eval forward
    (32, 2048, 128, 'bfloat16', True, 0, 0),     # the newer configurations'
    (8, 8192, 128, 'float32', True, 0, 0),   # K and V in four blocks
    (48, 512, 64, 'bfloat16', False, 12, 0),     # BERT: one tile, padding bias
    (48, 128, 64, 'float32', False, 12, 0),
    # the packed product [bh / packed, L, 3 x packed x dh]
    (64, 2048, 64, 'bfloat16', True, 0, 16),     # fd355m-train-2k
    (32, 2048, 64, 'bfloat16', True, 0, 32),     # fd1.3b-train-4chip, a chip
    (64, 2048, 64, 'float32', True, 0, 16),      # fd355m's eval forward
    (32, 2048, 128, 'bfloat16', True, 0, 8),     # one head of 128 a block
])
def test_mosaic_accepts_the_kernels_at_the_cells_shapes(one_chip, bh, ln, dh,
                                                        dtype, causal,
                                                        heads, packed):
    from paddle_tpu.ops import attention_ops as A

    def sds(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)
    scale = dh ** -0.5
    if packed:
        b = bh // packed
        x = [sds((b, ln, 3 * packed * dh))]
        o, row = sds((b, ln, packed * dh)), sds((b, packed, ln), 'float32')
    else:
        x = [sds((bh, ln, dh))] * 3
        o, row = x[0], sds((bh, ln), 'float32')
    bias = [sds((bh // heads, ln), 'float32')] if heads else []
    n = len(x)

    def fwd(*a):
        q, k, v = a[:n] + (None,) * (3 - n)
        return A._flash_fwd_pallas(q, k, v, scale, causal, False,
                                   bias=a[n] if bias else None,
                                   n_heads=heads or 1, heads=packed)

    def bwd(*a):
        q, k, v = a[:n] + (None,) * (3 - n)
        return A._flash_bwd_pallas(q, k, v, *a[n:n + 3], scale, causal,
                                   False, bias=a[n + 3] if bias else None,
                                   n_heads=heads or 1, heads=packed)
    text = jax.jit(fwd).lower(*x, *bias).compile().as_text()
    assert text.count('tpu_custom_call') >= 1
    text = jax.jit(bwd).lower(*x, o, row, o, *bias).compile().as_text()
    assert text.count('tpu_custom_call') >= 2


def test_kernbench_flash_attention_case(capsys):
    """tools/kernbench.py's `flash_attention` case at its toy shape through
    the interpreter: the rule's column and a stated tiling, the three
    kernels, the three behind one another and the whole between the fused
    product and the projection, and the JSON line the CLI prints."""
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
    rule = 'rule: 256,256 / 256,256 / 256,256'
    # a tiling a column pair: head-major operands, and the packed product
    assert set(row) == {rule, '128,128', 'packed ' + rule, 'packed 128,128'}
    for col in row.values():
        assert set(col) == {'fwd', 'bwd_dq', 'bwd_dkv', 'vjp', 'whole'}
        for kern in col.values():
            assert kern['ms'] > 0 and 'error' not in kern
