"""Fused-kernel tier selection: PADDLE_FUSED_TIER + per-op dispatch.

The kernel tier decides HOW a fusable op lowers (SURVEY §2.4: the
reference's operators/fused/ + jit/ runtime-codegen layer picks a kernel
per op; here one knob picks the lowering family for every fused unit):

- ``off``      — the unfused composition, bit-identical to the lowering
                 that existed before the fused tier (the parity anchor:
                 ``PADDLE_FUSED_TIER=off`` reproduces legacy numerics).
- ``xla``      — a restructured single-expression emission that avoids
                 materializing large intermediates and leans on XLA's own
                 fusion (e.g. the one-hot-free cross-entropy backward, the
                 flattened whole-parameter-set Adam update). Also accepted
                 as ``xla-fused``.
- ``pallas``   — the hand-written Pallas kernels (TPU).
- ``interpret``— the same Pallas kernels through the interpreter
                 (CPU-testable cross-check, like attention's
                 ``use_pallas='interpret'``).

Default (unset/auto): ``pallas`` on a TPU backend, ``off`` elsewhere — CPU
test suites see legacy numerics unless they opt in. Flash attention
(ops/attention_ops.py) resolves through the same knob: ``pallas`` /
``interpret`` run its kernels, ``off`` / ``xla`` the einsum reference.

Dispatch is resolved at TRACE time (op lowerings consult it while the
program compiles), so steady-state dispatch costs nothing per run; the
executor folds :func:`cache_token` — one env read — into its compile-cache
keys so flipping the knob recompiles instead of serving stale kernels.
Every resolution lands in the ``fused_kernel_dispatch_total{op,impl,mesh}``
counter (``mesh='1'`` single-device, ``'n'`` under an active >1-device
mesh), so bench counter deltas and obsreport show which tier actually
ran (and when a shape forced a per-op fallback).

Mesh-native fused units partition through :func:`partitioned_call` — the
shard_map-over-mesh wrapper extracted from ops/attention_ops.py, so every
fused unit shards the way flash attention already does instead of falling
back to the xla tier the moment a mesh is active.
"""
import os

import jax

from .. import monitor

__all__ = ['resolve_tier', 'dispatch', 'cache_token', 'TIERS',
           'partitioned_call', 'mesh_axis']

TIERS = ('off', 'xla', 'pallas', 'interpret')

_ALIASES = {
    '': None, 'auto': None, 'default': None,
    'off': 'off', '0': 'off', 'none': 'off',
    'xla': 'xla', 'xla-fused': 'xla', 'xla_fused': 'xla', '1': 'xla',
    'pallas': 'pallas',
    'interpret': 'interpret',
}


def resolve_tier():
    """The requested tier: env override, else pallas on TPU / off on CPU."""
    raw = os.environ.get('PADDLE_FUSED_TIER', '')
    tier = _ALIASES.get(str(raw).strip().lower(), '__bad__')
    if tier == '__bad__':
        raise ValueError(
            "PADDLE_FUSED_TIER=%r: expected one of off|xla|pallas|interpret"
            % (raw,))
    if tier is not None:
        return tier
    return 'pallas' if jax.default_backend() == 'tpu' else 'off'


def cache_token():
    """The NORMALIZED tier spelling, for compile-cache keys (env read +
    one alias-dict read — the only per-run cost of the fused tier on the
    Executor hot path; backend probing and counters happen at trace
    time). Normalizing means 'off'/'0'/'none' (or ''/'auto') share cache
    entries instead of forcing a recompile over a spelling change; an
    unknown value keys as itself and raises at the next trace."""
    raw = os.environ.get('PADDLE_FUSED_TIER', '')
    return _ALIASES.get(str(raw).strip().lower(), raw)


def dispatch(op, pallas_ok=True, xla_ok=True, tier=None, count=True,
             mesh=None):
    """Resolve the impl for one fused unit and count the decision.

    ``pallas_ok``: the shapes tile for the Pallas kernel (when False, a
    pallas/interpret request degrades to the xla tier — the per-op
    fallback rule); ``xla_ok``: the restructured emission supports this
    op instance (else everything degrades to 'off'). ``count=False``
    skips the counter — used by lowerings re-entered on the sparse-grad
    SCOUT pass (core/lowering.py lowers the forward segment twice for
    is_sparse programs; counting both would double every dispatch the
    bench deltas report). ``mesh``: the active mesh (or None) — labels
    the counter ``mesh='n'`` when the decision ran under a >1-device
    mesh, so sharded bench rows prove which impl actually partitioned.
    Returns one of 'off' | 'xla' | 'pallas' | 'interpret'.
    """
    impl = tier if tier is not None else resolve_tier()
    if impl in ('pallas', 'interpret') and not pallas_ok:
        impl = 'xla'
    if impl == 'xla' and not xla_ok:
        impl = 'off'
    if count:
        meshed = mesh is not None and getattr(mesh, 'size', 1) > 1
        monitor.inc('fused_kernel_dispatch_total',
                    labels={'op': op, 'impl': impl,
                            'mesh': 'n' if meshed else '1'})
    return impl


# ---------------------------------------------------------------------------
# SPMD: the shared shard_map-over-mesh wrapper (extracted from
# ops/attention_ops.py so every fused unit partitions the way flash
# attention does)
# ---------------------------------------------------------------------------

def partitioned_call(fn, mesh, in_specs, out_specs):
    """shard_map ``fn`` over ``mesh`` with the given PartitionSpecs — one
    kernel invocation per shard, XLA stitching the shards back together.
    Manual over all mesh axes; axes a spec does not name see replicated
    data, so e.g. a data-only spec under mesh(data=2, model=2) runs the
    same per-shard kernel on both model rows. A pallas custom call
    cannot be auto-partitioned by the XLA SPMD partitioner — this
    wrapper is what lets the fused tier survive an active mesh at all."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def mesh_axis(mesh, name, dim_size):
    """Mesh axis ``name`` if present, >1, and divides ``dim_size``; else
    None (the caller leaves that dimension unsharded)."""
    if name in mesh.axis_names and mesh.shape[name] > 1 \
            and dim_size % mesh.shape[name] == 0:
        return name
    return None
