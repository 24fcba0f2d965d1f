"""gpipe_run: the meta-op emitted by transpiler.PipelineTranspiler.

One op holding the program's repeated layer run. Without a 'pipe' mesh
axis it lowers to the serial layer loop (identical math to the original
program); under a MeshRunner mesh with a 'pipe' axis it lowers to the
lax.ppermute microbatch pipeline (parallel/pipeline.py gpipe) — stage
parameters are stacked [n_stages, layers_per_stage, ...] inside the trace,
so jax.vjp delivers per-layer gradients to the original parameter names
and the program's optimizer ops run unchanged.

No reference counterpart: fluid ~1.3 has no pipeline parallelism (SURVEY
§2.7); this is the TPU-native extension at Program level.
"""
import jax
import jax.numpy as jnp

from ..core.registry import register_op

_composition_logged = set()


def _log_once(key, message):
    """One-time composition diagnostics: silent fallbacks to full-batch
    replication are correct but lose the sharding win — say so, once."""
    if key in _composition_logged:
        return
    _composition_logged.add(key)
    import logging
    logging.getLogger('paddle_tpu.pipeline').warning(message)


def _bindings(op):
    slot_names = list(op.attr('slot_names'))
    flat = list(op.attr('bindings_flat'))
    n_layers = int(op.attr('n_layers'))
    e = len(slot_names)
    assert len(flat) == n_layers * e, (len(flat), n_layers, e)
    return slot_names, [flat[k * e:(k + 1) * e] for k in range(n_layers)]


def _lower_segment(ctx, sub, env, key):
    """Trace the layer-0 segment ops with `env` bindings; returns the
    segment's env after lowering."""
    from ..core.lowering import lower_ops
    child = ctx.child(env, block=sub)
    child.base_key = key
    lower_ops(child, sub.ops, 0, len(sub.ops))
    return child.env


@register_op('gpipe_run', needs_rng=True)
def _gpipe_run(ctx, op):
    from ..parallel.api import get_active_mesh
    sub = ctx.program.block(int(op.attr('sub_block')))
    n_layers = int(op.attr('n_layers'))
    # a boundary may carry K tensors (residual trunk + branch, h/c pairs);
    # legacy single-activation programs carry in_var/out_var
    in_vars = list(op.attr('in_vars') or [op.attr('in_var')])
    out_vars = list(op.attr('out_vars') or [op.attr('out_var')])
    shared = list(op.attr('shared_names') or [])
    slot_names, bindings = _bindings(op)

    act = tuple(ctx.get(n) for n in op.input('X'))
    shared_vals = {n: ctx.get(n) for n in shared}
    base_key = ctx.rng()

    mesh = get_active_mesh()
    n_stages = int(op.attr('num_stages'))
    pipelined = mesh is not None and mesh.shape.get('pipe', 1) > 1
    if pipelined and mesh.shape['pipe'] != n_stages:
        raise ValueError(
            "gpipe_run was transpiled for %d stages but the mesh 'pipe' "
            "axis has size %d" % (n_stages, mesh.shape['pipe']))

    if not pipelined:
        # serial fallback: the original layer loop, same math
        for k in range(n_layers):
            env = dict(shared_vals)
            env.update(zip(in_vars, act))
            for sname, real in zip(slot_names, bindings[k]):
                env[sname] = ctx.get(real)
            seg_env = _lower_segment(ctx, sub, env,
                                     jax.random.fold_in(base_key, k))
            act = tuple(seg_env[n] for n in out_vars)
        for j, n in enumerate(op.output('Out')):
            ctx.set(n, act[j])
        return

    from ..parallel.pipeline import gpipe
    lps = n_layers // n_stages
    # stack each external slot over layers -> [S, lps, ...]; stacking
    # happens inside the trace, so AD routes the stacked cotangent back to
    # each layer's own parameter name
    stacked = tuple(
        jnp.stack([ctx.get(bindings[k][e]) for k in range(n_layers)])
        .reshape((n_stages, lps) + tuple(
            jnp.shape(ctx.get(bindings[0][e]))))
        for e in range(len(slot_names)))

    def stage_fn(params, x, extra):
        from jax import lax
        from ..parallel import api as _papi
        s = lax.axis_index('pipe')
        # the stage body runs per device inside shard_map (manual mesh):
        # ops must lower single-device — nested SPMD dispatch (e.g. the
        # flash-attention shard_map path) would see a mismatched mesh
        prev, _papi._ACTIVE_MESH = _papi._ACTIVE_MESH, None
        try:
            for jj in range(lps):
                env = dict(extra)
                env.update(zip(in_vars, x))
                for e, sname in enumerate(slot_names):
                    env[sname] = params[e][jj]
                key = jax.random.fold_in(base_key, s * lps + jj)
                seg_env = _lower_segment(ctx, sub, env, key)
                x = tuple(seg_env[n] for n in out_vars)
        finally:
            _papi._ACTIVE_MESH = prev
        return x

    # compose with data parallelism when the mesh carries a 'data' axis:
    # microbatch rows shard over it and param cotangents psum over it
    # (parallel/pipeline.py batch_axis) — falls back to replication when
    # the per-microbatch row count does not divide the axis. The axis-name
    # contract ('data', literally) and the divisibility rule are
    # documented in docs/parallelism.md.
    n_micro = int(op.attr('num_microbatches') or 0) or n_stages
    batch_axis = None
    if mesh.shape.get('data', 1) > 1:
        b0 = int(jnp.shape(act[0])[0])
        if b0 % n_micro == 0 and (b0 // n_micro) % mesh.shape['data'] == 0:
            batch_axis = 'data'
    if batch_axis is None and any(
            mesh.shape[a] > 1 for a in mesh.axis_names if a != 'pipe'):
        _log_once(('noengage', tuple(sorted(mesh.axis_names)), n_micro),
                  "gpipe_run: mesh %s has a >1 non-pipe axis but batch "
                  "composition did NOT engage — it requires an axis "
                  "literally named 'data' whose size divides "
                  "B//num_microbatches (see docs/parallelism.md). The "
                  "batch is replicated per non-pipe device: correct "
                  "math, duplicated compute."
                  % dict(mesh.shape))
    out = gpipe(stage_fn, stacked, act, mesh,
                num_microbatches=n_micro, extra=shared_vals,
                batch_axis=batch_axis)
    for j, n in enumerate(op.output('Out')):
        ctx.set(n, out[j])
