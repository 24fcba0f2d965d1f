"""Every op that reads a slot's pages outside a Pallas kernel takes them in
ONE gather on (block, layer) — `kv_cache_ops.pool_pages` — and never makes
a copy of a layer's share of the whole pool first (PR 42: on the TPU
``cache[:, layer][tables]`` was a slice of ``num_blocks`` pages, for K and
for V, in every layer of every prefill).

Two things a case: the traced op holds no intermediate value with
``num_blocks`` among its dimensions, and its output is bit for bit what the
slice-then-gather spelling gives, which stays here as the plain reference.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import get_op
from paddle_tpu.ops import kv_cache_ops, mla_ops

from test_olmoe_serving import _Ctx, _Op

# no other dimension of any case is 64; a table names four pages
NB, LAYERS, BS, DH = 64, 3, 8, 8
LAYER = 1
# a repeated page, the trash block, the pool's last block
TABLE = np.array([7, 0, 7, 63], 'int32')
TABLES = np.array([[7, 0, 7, 63], [1, 2, 3, 0], [5, 5, 5, 5]], 'int32')
POS = np.array([29, 17, 31], 'int32')


def _slice_then_gather(cache, layer, tables):
    """What `pool_pages` was until PR 42."""
    return cache[:, layer][tables]


class _TracedCtx(_Ctx):
    """`_Ctx` that keeps an output as it is: under `make_jaxpr` a tracer."""

    def out(self, op, slot, value):
        self.outs[slot] = value


def _lowered(op_type, **attrs):
    def fn(**ins):
        ctx = _TracedCtx(**ins)
        get_op(op_type).lower(ctx, _Op(layer=LAYER, block_size=BS, **attrs))
        return ctx.outs['Out']
    return fn


def _pool(rng, width):
    return rng.randn(NB, LAYERS, BS, width).astype('float32')


def _prefix(rng, heads, kv_heads):
    rows = 5
    return _lowered('kv_prefix_attention', scale=DH ** -0.5), dict(
        Q=rng.randn(1, heads, rows, DH).astype('float32'),
        KCache=_pool(rng, kv_heads * DH), VCache=_pool(rng, kv_heads * DH),
        BlockTable=TABLE[None], Positions=np.arange(20, 20 + rows)[None])


def _verify(rng):
    span = 3
    return _lowered('kv_verify_attention_paged', scale=DH ** -0.5), dict(
        Q=rng.randn(len(POS), 4, span, DH).astype('float32'),
        KCache=_pool(rng, 4 * DH), VCache=_pool(rng, 4 * DH),
        BlockTables=TABLES, Positions=POS[:, None] - span + 1 + np.arange(span))


def _decode(rng, impl, heads, kv_heads):
    def fn(q, kc, vc, tables, pos):
        return kv_cache_ops._decode_attention(
            impl, q, kc, vc, tables, pos, LAYER, DH ** -0.5, BS, None)
    return fn, dict(q=rng.randn(len(POS), heads, DH).astype('float32'),
                    kc=_pool(rng, kv_heads * DH), vc=_pool(rng, kv_heads * DH),
                    tables=TABLES, pos=POS)


# the latent pool: a row is [latent | rotary key], 24 lanes
RANK, ROPE, NOPE, VDIM, LATENT_HEADS = 16, 8, 12, 10, 6


def _mla_prefix(rng):
    rows = 5
    return _lowered('mla_prefix_attention', scale=0.2), dict(
        Q=rng.randn(1, rows, LATENT_HEADS, NOPE + ROPE).astype('float32'),
        Cache=_pool(rng, RANK + ROPE),
        UpK=rng.randn(LATENT_HEADS, NOPE, RANK).astype('float32'),
        UpV=rng.randn(LATENT_HEADS, RANK, VDIM).astype('float32'),
        BlockTable=TABLE[None], Positions=np.arange(20, 20 + rows)[None])


def _absorbed(rng):
    def fn(q, pool, tables, pos):
        return mla_ops.absorbed_decode_reference(q, pool, tables, pos, LAYER,
                                                 0.2, RANK)
    return fn, dict(
        q=rng.randn(len(POS), LATENT_HEADS, RANK + ROPE).astype('float32'),
        pool=_pool(rng, RANK + ROPE), tables=TABLES, pos=POS)


CASES = {
    'kv_prefix_attention': lambda rng: _prefix(rng, 4, 4),
    'kv_prefix_attention-grouped': lambda rng: _prefix(rng, 4, 2),
    'kv_verify_attention_paged': _verify,
    'kv_decode_attention_paged-xla': lambda rng: _decode(rng, 'xla', 4, 4),
    'kv_decode_attention_paged-off': lambda rng: _decode(rng, 'off', 4, 4),
    'kv_decode_attention_paged-xla-grouped':
        lambda rng: _decode(rng, 'xla', 4, 2),
    'mla_prefix_attention': _mla_prefix,
    'absorbed_decode_reference': _absorbed,
}


def _case(name):
    fn, ins = CASES[name](np.random.RandomState(len(name)))
    return fn, {k: jnp.asarray(v) for k, v in ins.items()}


def _sub_jaxprs(value):
    if hasattr(value, 'jaxpr'):                 # a ClosedJaxpr
        yield value.jaxpr
    elif hasattr(value, 'eqns'):
        yield value
    elif isinstance(value, (list, tuple)):
        for v in value:
            for sub in _sub_jaxprs(v):
                yield sub


def _pool_sized(jaxpr):
    """(primitive, shape) of every value an equation of `jaxpr` — or of a
    jaxpr inside it (`pjit`, `scan`, `cond`) — makes with ``NB`` among
    its dimensions."""
    found = []
    for eqn in jaxpr.eqns:
        found += [(eqn.primitive.name, v.aval.shape) for v in eqn.outvars
                  if NB in getattr(v.aval, 'shape', ())]
        for value in eqn.params.values():
            for sub in _sub_jaxprs(value):
                found += _pool_sized(sub)
    return found


def _old_form(monkeypatch):
    """Puts the slice-then-gather spelling in `pool_pages`' place; the
    list it returns holds one entry for every call that took it."""
    calls = []

    def counted(cache, layer, tables):
        calls.append(layer)
        return _slice_then_gather(cache, layer, tables)
    monkeypatch.setattr(kv_cache_ops, 'pool_pages', counted)
    monkeypatch.setattr(mla_ops, 'pool_pages', counted)
    return calls


@pytest.mark.parametrize('name', sorted(CASES))
def test_no_value_of_the_traced_op_is_as_long_as_the_pool(name, monkeypatch):
    fn, ins = _case(name)
    assert _pool_sized(jax.make_jaxpr(fn)(**ins).jaxpr) == []
    # the walk does see the layer's slice where an op makes one (a new
    # function object: `make_jaxpr` keeps the trace of one it has seen)
    calls = _old_form(monkeypatch)
    made = _pool_sized(jax.make_jaxpr(lambda **kw: fn(**kw))(**ins).jaxpr)
    assert calls and len(made) >= len(calls), (calls, made)
    assert all(shape[0] == NB for _, shape in made), made


@pytest.mark.parametrize('name', sorted(CASES))
def test_the_output_is_the_slice_then_gather_forms_bit_for_bit(
        name, monkeypatch):
    fn, ins = _case(name)
    got = np.asarray(fn(**ins))
    calls = _old_form(monkeypatch)
    want = np.asarray(fn(**ins))
    assert calls
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.isfinite(got).all() and np.abs(got).max() > 0
    np.testing.assert_array_equal(got, want)
