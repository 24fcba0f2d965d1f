"""Whole-program lowering: Program IR -> one jax-traced, XLA-compiled callable.

This module replaces three reference subsystems at once, the TPU-idiomatic way:

- the serial Executor's interpret loop (reference framework/executor.cc:432-440
  `for op in ops: op->Run(scope, place)`) -> a single traced function compiled
  once by XLA; feed/fetch become function inputs/outputs;
- per-op kernel dispatch (reference framework/operator.cc:907-960) -> each op's
  registered `lower` emits jax/lax ops into the trace; XLA fuses and schedules
  (subsuming the ir-pass fusions of reference framework/ir/*fuse_pass*);
- desc-level autodiff (reference python backward.py:394 append_backward calling
  C++ grad-op makers) -> the meta op `backward` runs the forward segment inside
  jax.vjp, so gradients are computed by JAX reverse-mode AD with XLA-optimal
  rematerialization, not by stitching grad-op descs.

Random ops draw keys deterministically from a per-run base key folded with the
op's index, so replaying a segment inside the vjp closure sees identical
randomness (dropout masks match between forward env and grad closure).
"""
import contextlib
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .registry import get_op
from .. import coldstart
from .. import monitor


# ---------------------------------------------------------------------------
# op lowering hook (analysis.py: op-level attribution + NaN provenance)
#
# A hook wraps every op lowering as `hook(ctx, op, thunk)` where `thunk()`
# performs the actual lowering (and, when the program runs EAGERLY — the
# interpreting path analysis.run_profiled uses — the actual computation).
# Thread-local so one thread's profiling replay never instruments another
# thread's trace; checked once per op at TRACE time, so compiled steady-state
# dispatch pays nothing.

_op_hook_tls = threading.local()


def _active_op_hook():
    return getattr(_op_hook_tls, 'fn', None)


@contextlib.contextmanager
def op_hook(fn):
    """Install `fn(ctx, op, thunk)` around every op lowered on THIS thread
    for the duration of the block (hooks do not nest — the inner hook
    wins, the outer is restored on exit)."""
    prev = getattr(_op_hook_tls, 'fn', None)
    _op_hook_tls.fn = fn
    try:
        yield
    finally:
        _op_hook_tls.fn = prev


class LowerContext(object):
    """Mutable environment while tracing one block: var name -> jax value."""

    def __init__(self, program, block, env, base_key, wrt=(), params=None,
                 lods=None, statics=None, op_offset=0):
        self.program = program
        self.block = block
        self.env = env
        self.base_key = base_key
        self.op_index = 0
        # rng() folds op_offset + op_index: a host-op segment (executor
        # _run_segmented) slices the global block at plo, so its offset is
        # plo — making per-op PRNG keys identical to the unsegmented
        # program's. NOT inherited by child (sub-block) contexts: child
        # blocks keep their own indexing in both execution modes.
        self.op_offset = op_offset
        self.wrt = set(wrt)
        # extra knobs lowerings may consult
        self.params = params or {}
        # static LoD metadata: var name -> tuple of offset tuples. Shared
        # (same dict object) across child contexts — lods are compile-time
        # constants, so mutation at trace time is idempotent per cache entry.
        self.lods = lods if lods is not None else {}
        # names whose lod was set (or cleared) explicitly by an op lowering —
        # exempt from default ShareLoD propagation
        self.lod_explicit = set()
        # compile-time-constant feed values (numpy) for shape-bearing inputs
        self.statics = statics if statics is not None else {}
        # statics recorded by the op currently lowering (lower_ops drops
        # stale statics for outputs the op did NOT re-declare — e.g. an
        # increment overwriting a fill_constant's recorded value)
        self._static_written = set()
        # NHWC layout twins: var name -> the producer's channels-minor
        # value BEFORE the NCHW-restoring transpose. Conv/pool/BN/
        # elementwise lowerings read + write twins so vision stacks stay
        # channels-minor end-to-end (measured ~5x on v5e); env[] always
        # holds the public NCHW value and XLA dead-code-eliminates
        # whichever representation nothing consumes. Twins are PER
        # CONTEXT (never shared across trace scopes — a cross-jit twin
        # would leak tracers).
        self.nhwc = {}
        self._twin_written = set()

    # ---- reading inputs --------------------------------------------------
    def has(self, name):
        return name in self.env

    def get(self, name):
        try:
            return self.env[name]
        except KeyError:
            raise KeyError(
                "variable %r used before definition while lowering op #%d "
                "(%s) — is it fed / initialized?" %
                (name, self.op_index, self.block.ops[self.op_index].type))

    def in1(self, op, slot, default=None):
        names = op.input(slot)
        if not names:
            return default
        return self.get(names[0])

    def in_list(self, op, slot):
        return [self.get(n) for n in op.input(slot)]

    # ---- writing outputs -------------------------------------------------
    def set(self, name, value):
        var = self.block._find_var_recursive(name)
        if var is not None and var.stop_gradient and name not in self.wrt:
            value = lax.stop_gradient(value)
        self.env[name] = value

    def out(self, op, slot, value, idx=0):
        names = op.output(slot)
        if not names:
            return
        self.set(names[idx], value)

    def var(self, name):
        return self.block._find_var_recursive(name)

    # ---- NHWC layout twins ----------------------------------------------
    def in_nhwc(self, op, slot, default=None):
        """Channels-minor view of a 4-d input: the producer's NHWC twin
        when one exists, else a transpose of the NCHW env value (which
        XLA cancels against the producer's own transpose)."""
        names = op.input(slot)
        if not names:
            return default
        n = names[0]
        if n in self.nhwc:
            return self.nhwc[n]
        v = self.get(n)
        return jnp.transpose(v, (0, 2, 3, 1))

    def has_nhwc(self, op, slot):
        names = op.input(slot)
        return bool(names) and names[0] in self.nhwc

    def out_nhwc(self, op, slot, value_nhwc, idx=0):
        """Emit a 4-d output from its NHWC value: env gets the NCHW
        transpose (public contract), the twin table keeps the NHWC
        original for layout-aware consumers."""
        names = op.output(slot)
        if not names:
            return
        n = names[idx]
        var = self.block._find_var_recursive(n)
        if var is not None and var.stop_gradient and n not in self.wrt:
            value_nhwc = lax.stop_gradient(value_nhwc)
        self.env[n] = jnp.transpose(value_nhwc, (0, 3, 1, 2))
        self.nhwc[n] = value_nhwc
        self._twin_written.add(n)

    # ---- static LoD / static values --------------------------------------
    def lod_of(self, name):
        """The (static) LoD of a variable, or () if it is dense."""
        return self.lods.get(name, ())

    def set_lod(self, name, lod):
        from .lod import normalize_lod
        lod = normalize_lod(lod)
        self.lod_explicit.add(name)
        if lod:
            self.lods[name] = lod
        else:
            self.lods.pop(name, None)

    def in1_lod(self, op, slot):
        names = op.input(slot)
        return self.lods.get(names[0], ()) if names else ()

    def set_static(self, name, value):
        """Record a trace-time-constant value for a produced output (e.g.
        sequence_pad's Length, a pure function of the static LoD), so
        static_inputs consumers downstream can bind it."""
        self.statics[name] = np.asarray(value)
        self._static_written.add(name)

    def static_value(self, name):
        """Concrete numpy value of a shape-bearing input. Available for feeds
        declared via the op's `static_inputs`, or when the producing op
        recorded it via set_static."""
        if name in self.statics:
            return self.statics[name]
        if name in self.env:
            v = self.env[name]
            if not isinstance(v, jax.core.Tracer):
                return np.asarray(v)
        raise ValueError(
            "op #%d (%s) needs the concrete value of %r at trace time "
            "(its output layout depends on it, like dynamic shapes under "
            "XLA). Feed it so the executor can bind it statically."
            % (self.op_index, self.block.ops[self.op_index].type, name))

    def in1_static(self, op, slot, default=None):
        names = op.input(slot)
        if not names:
            return default
        return self.static_value(names[0])

    # ---- rng -------------------------------------------------------------
    def rng(self):
        key = jax.random.fold_in(self.base_key,
                                 self.op_offset + self.op_index)
        seed = self.program.random_seed
        if seed:
            key = jax.random.fold_in(key, seed)
        return key

    def child(self, env, wrt=None, block=None):
        # SAME-block children (backward vjp spans, recompute) keep this
        # context's op_offset so their lower_ops indices stay global;
        # sub-BLOCK children reset to 0 — child blocks fold their own
        # indexing identically in segmented and unsegmented execution.
        c = LowerContext(self.program,
                         self.block if block is None else block,
                         env, self.base_key,
                         wrt=self.wrt if wrt is None else wrt,
                         params=self.params, lods=self.lods,
                         statics=self.statics,
                         op_offset=self.op_offset if block is None else 0)
        return c


_lowering_open = {}      # thread id -> the innermost open _OpClock
# a label set an op type: a train step lowers ~80
monitor.set_series_cap('program_lowering_seconds_total', 512)


class _OpClock(object):
    """The SELF time in Python of one IR op's lowering, into
    program_lowering_seconds_total{op_type}: what an op type's lowering
    costs every process that traces it — its own tracing, and JAX's of
    what it calls. An op lowered inside it (a `*_grad` op's forward
    through ctx.child, a sub-block's ops) books its own and is taken out.
    Two clock reads an IR op, at trace time only. A `with` block and no
    wrapper: the lowering's Python stack — which JAX writes into every
    traced equation's location, and a Mosaic kernel's body carries into
    the compile cache's key — stays what it is without the clock."""

    __slots__ = ('op_type', 'outer', 'nested_s', 't0')

    def __init__(self, op_type):
        self.op_type = op_type

    def __enter__(self):
        tid = threading.get_ident()
        self.outer = _lowering_open.get(tid)
        _lowering_open[tid] = self
        self.nested_s = 0.0
        self.t0 = time.perf_counter()

    def __exit__(self, *exc):
        whole = time.perf_counter() - self.t0
        outer = self.outer
        if outer is None:
            del _lowering_open[threading.get_ident()]
        else:
            _lowering_open[threading.get_ident()] = outer
            outer.nested_s += whole
        monitor.inc('program_lowering_seconds_total', whole - self.nested_s,
                    {'op_type': self.op_type})
        return False


_NO_SCOPE = contextlib.nullcontext()


def _trace_scope(name):
    """The named scope an op with a ``trace_scope`` attribute lowers
    under, prefixed as the package's host annotations are."""
    return _NO_SCOPE if name is None \
        else jax.named_scope(monitor.ANNOTATION_PREFIX + name)


def lower_ops(ctx, ops, lo, hi):
    hook = _active_op_hook()
    for i in range(lo, hi):
        ctx.op_index = i
        op = ops[i]
        ctx._static_written = set()
        ctx._twin_written = set()
        # an op that says which part of its program it belongs to (a
        # looped model's pass: models/transformer.py `_loop_pass`) lowers
        # under that name, which its HLO's metadata then carries
        with _trace_scope(op.attrs.get('trace_scope')):
            if hook is None:
                with _OpClock(op.type):
                    get_op(op.type).lower(ctx, op)
            else:
                hook(ctx, op, lambda op=op: get_op(op.type).lower(ctx, op))
        for n in op.output_arg_names:
            if n not in ctx._static_written:
                ctx.statics.pop(n, None)
            if n not in ctx._twin_written:
                # a layout-unaware op rewrote this name: its old NHWC twin
                # no longer matches the env value
                ctx.nhwc.pop(n, None)
        _share_lod(ctx, op)


def _share_lod(ctx, op):
    """Default LoD propagation (reference InferShapeContext::ShareLoD: most
    elementwise-ish ops share their first input's LoD with outputs). An op
    that set (or cleared) an output's lod explicitly wins; ops registered
    with share_lod=False (rows permuted/selected/reinterpreted — transpose,
    gather, reverse, ...) never inherit; otherwise any output whose leading
    dim matches a lod-carrying input's leading dim inherits that input's
    lod."""
    if not get_op(op.type).share_lod:
        return
    in_lod = None
    lead = None
    for n in op.input_arg_names:
        lod = ctx.lods.get(n)
        if lod and ctx.has(n):
            v = ctx.env[n]
            if getattr(v, 'ndim', 0) >= 1:
                in_lod, lead = lod, v.shape[0]
                break
    if in_lod is None:
        return
    for n in op.output_arg_names:
        if n in ctx.lods or n in ctx.lod_explicit or not ctx.has(n):
            continue
        v = ctx.env[n]
        if getattr(v, 'ndim', 0) >= 1 and v.shape[0] == lead:
            ctx.lods[n] = in_lod


def lower_block(ctx, lo=0):
    """Lower ops [lo:] of ctx.block, handling `backward` meta ops.

    When a `backward` op is found at index b, ops [lo:b] are lowered inside a
    jax.vjp closure (so forward activations are traced exactly once, and JAX
    AD produces the gradients); the resulting env replaces ctx.env and
    lowering continues after the backward op (optimizer ops etc.).
    """
    ops = ctx.block.ops
    b = next((i for i in range(lo, len(ops)) if ops[i].type == 'backward'),
             None)
    if b is None:
        lower_ops(ctx, ops, lo, len(ops))
        return

    bop = ops[b]
    ctx.op_index = b
    hook = _active_op_hook()
    if hook is None:
        # the pullback — JAX's transposition, every kernel's backward
        # rule — is `backward`'s own; the forward ops under the vjp book
        # theirs
        with _OpClock('backward'):
            _lower_backward(ctx, ops, lo, b, bop)
    else:
        # the whole differentiated span (forward-under-vjp + pullback +
        # grad binding) attributes to the `backward` op: its interior ops
        # execute under jax.vjp tracing, so per-op hooks inside see
        # tracers — analysis.py's provenance pass scouts the forward
        # segment concretely on its own when it needs op-exact blame
        hook(ctx, bop, lambda: _lower_backward(ctx, ops, lo, b, bop))
    lower_block(ctx, b + 1)


def _lower_backward(ctx, ops, lo, b, bop):
    loss_name = bop.input('Loss')[0]
    wrt_names = list(bop.attr('wrt_names'))
    sparse_set = set(bop.attr('sparse_wrt') or ())
    dense_wrt = [n for n in wrt_names if n not in sparse_set]
    base_env = dict(ctx.env)

    missing = [n for n in wrt_names if n not in base_env]
    if missing:
        if any('@ps_rows' in n for n in missing):
            # PS-remote rows feeds (ps/program.py) are dense wrt LEAVES:
            # the pullback's cotangent w.r.t. the fed rows is the row
            # gradient the trainer pushes — but only a PS-aware driver
            # feeds them
            raise ValueError(
                "backward: PS rows feeds %s were not supplied — drive "
                "this program through ps.PSTrainerSession (or feed the "
                "pulled rows yourself); a plain Executor.run cannot "
                "train a pserver-transpiled program"
                % [n for n in missing if '@ps_rows' in n])
        raise ValueError(
            "backward: cannot differentiate w.r.t. %s — they are neither fed "
            "nor in scope state (only leaf variables are supported)" % missing)

    # Sparse-embedding grads (reference lookup_table_op.cc is_sparse path):
    # the table never enters the vjp wrt set, so AD never materializes a
    # dense [vocab, dim] gradient. A scout lowering of the forward segment
    # records each sparse lookup site's flattened ids (pure functions of the
    # feeds — XLA DCEs the scout's dead outputs); the real forward then adds
    # a zero-valued "dummy" of the gathered-rows shape at each site, and the
    # pullback's dummy cotangents ARE the per-row gradients.
    sites = []
    if sparse_set:
        sctx = ctx.child(dict(base_env))
        sctx.sparse_tables = sparse_set
        sctx.sparse_mode = 'scout'
        sctx.sparse_sites = sites
        lower_ops(sctx, ops, lo, b)

    wrt_vals = {n: base_env[n] for n in dense_wrt}
    for k, (tbl, flat_ids, dim, dtype) in enumerate(sites):
        wrt_vals['@sparse%d' % k] = jnp.zeros((flat_ids.shape[0], dim), dtype)

    ckpt_names = set(bop.attr('checkpoints') or ())

    def fwd(wrt_vals):
        env2 = dict(base_env)
        env2.update(wrt_vals)
        sub = ctx.child(env2, wrt=set(wrt_names))
        if sparse_set:
            sub.sparse_tables = sparse_set
            sub.sparse_mode = 'apply'
            sub.sparse_counter = [0]
        if ckpt_names and not sparse_set:
            _lower_with_remat(sub, ops, lo, b, ckpt_names)
        else:
            if ckpt_names and sparse_set:
                import warnings
                warnings.warn(
                    "append_backward(checkpoints=...) is ignored when "
                    "sparse (is_sparse=True) embedding gradients are in "
                    "the same program: the sparse scout/dummy mechanism "
                    "does not compose with jax.checkpoint segments yet",
                    stacklevel=2)
            lower_ops(sub, ops, lo, b)
        return env2[loss_name], env2

    (loss_val, env2), pullback = _vjp_with_aux(fwd, wrt_vals)
    # loss-cotangent seed: 1 by default; the DP runner sets
    # loss_grad_scale=num_devices for BuildStrategy.GradientScaleStrategy.One
    # (reference details/scale_loss_grad_op_handle.cc seeds 1/N per device
    # under CoeffNumDevice; our global-batch mean already folds in 1/N, so
    # One re-scales by N)
    seed_scale = ctx.params.get('loss_grad_scale', 1.0)
    grads = pullback(jnp.full_like(loss_val, seed_scale))

    per_table = {}
    for k, (tbl, flat_ids, dim, dtype) in enumerate(sites):
        per_table.setdefault(tbl, []).append(
            (flat_ids, grads['@sparse%d' % k]))

    ctx.env = env2
    from ..framework import grad_var_name
    from .selected_rows import SelectedRows
    grad_outs = bop.output('Grads')
    for i, n in enumerate(wrt_names):
        gname = grad_outs[i] if i < len(grad_outs) else grad_var_name(n)
        if n in sparse_set:
            pairs = per_table.get(n, [])
            height = base_env[n].shape[0]
            if not pairs:
                dim = base_env[n].shape[1]
                g = SelectedRows(jnp.full((1,), height, jnp.int32),
                                 jnp.zeros((1, dim), base_env[n].dtype),
                                 height)
            else:
                rows = jnp.concatenate([p[0] for p in pairs])
                vals = jnp.concatenate([p[1] for p in pairs])
                if len(pairs) > 1:
                    # XLA SPMD (jax 0.4.37) miscompiles a scatter-add whose
                    # indices/updates are a CONCAT of batch-sharded vectors
                    # when the operand is sharded on dim 0: shard-0 updates
                    # land at stride-N_shard global rows and other shards'
                    # vanish (repro: tests/test_sharded_embedding.py
                    # test_sharded_scatter_concat_partitioner). Pinning the
                    # concatenated rows AND values replicated restores the
                    # single-site partitioning, which is exact; rows/vals
                    # are batch-sized, never [vocab]-sized, so the
                    # all-gather is cheap next to the table itself.
                    rows, vals = _replicate_under_mesh(rows, vals)
                g = SelectedRows(rows, vals, height)
        else:
            g = grads[n]
        ctx.env[gname] = g


def _lower_with_remat(ctx, ops, lo, b, ckpt_names):
    """Rematerialization (reference append_backward(checkpoints=...) /
    the memory_optimize recompute strategy, realized the JAX way): the
    forward segment is split at ops producing checkpoint vars and each
    segment is traced under jax.checkpoint, so only segment boundaries are
    saved for the backward pass — HBM traded for recompute FLOPs.

    Segments containing control-flow sub-blocks or TensorArray writes run
    unwrapped (their env values are not plain arrays)."""
    # segment boundaries AFTER each op that produces a checkpoint var
    bounds = []
    for i in range(lo, b):
        if ckpt_names & set(ops[i].output_arg_names):
            bounds.append(i + 1)
    if not bounds:
        raise ValueError(
            "append_backward(checkpoints=...): none of %s is produced by "
            "this program's forward segment — stale vars from another "
            "program build? (each build_lm/model build creates fresh "
            "unique names)" % sorted(ckpt_names))
    if bounds[-1] != b:
        bounds.append(b)

    start = lo
    for end in bounds:
        _lower_segment(ctx, ops, start, end)
        start = end


class _NonArraySegmentOutput(Exception):
    pass


def _is_plain_array(v):
    import jax as _jax
    return isinstance(v, (_jax.Array, jnp.ndarray, np.ndarray, float, int)) \
        or hasattr(v, 'shape')


def _lower_segment(ctx, ops, s, e):
    if s >= e:
        return
    seg = ops[s:e]
    wrappable = all('sub_block' not in op.attrs and
                    op.type not in ('backward',)
                    for op in seg)
    if wrappable:
        in_names, seen = [], set()
        for op in seg:
            for n in op.input_arg_names:
                if n not in seen and ctx.has(n) and \
                        _is_plain_array(ctx.env[n]):
                    seen.add(n)
                    in_names.append(n)
        out_names, oseen = [], set()
        for op in seg:
            for n in op.output_arg_names:
                if n not in oseen:
                    oseen.add(n)
                    out_names.append(n)
        produced = []

        def seg_fn(*vals):
            env_l = dict(ctx.env)
            env_l.update(zip(in_names, vals))
            c2 = ctx.child(env_l)
            for attr in ('sparse_tables', 'sparse_mode', 'sparse_counter'):
                if hasattr(ctx, attr):
                    setattr(c2, attr, getattr(ctx, attr))
            # global op indices keep per-op RNG folds identical to the
            # unwrapped lowering (dropout masks match)
            lower_ops(c2, ops, s, e)
            bad = [n for n in out_names
                   if n in env_l and not _is_plain_array(env_l[n])]
            if bad:
                # TensorArrays etc. cannot cross a jax.checkpoint
                # boundary; surface to the caller's fallback path
                raise _NonArraySegmentOutput(bad)
            del produced[:]
            produced.extend(n for n in out_names if n in env_l)
            return tuple(env_l[n] for n in produced)

        try:
            results = jax.checkpoint(seg_fn)(
                *[ctx.env[n] for n in in_names])
        except _NonArraySegmentOutput as exc:
            import warnings
            warnings.warn(
                "remat: segment ops[%d:%d] produces non-array state %s "
                "(TensorArray etc.) and runs WITHOUT rematerialization"
                % (s, e, exc.args[0]), stacklevel=2)
            lower_ops(ctx, ops, s, e)
            return
        except Exception as exc:
            # anything jax.checkpoint cannot trace (trace-time statics,
            # host callbacks, ...): fall back, but never silently
            import warnings
            warnings.warn(
                "remat: segment ops[%d:%d] could not be wrapped in "
                "jax.checkpoint (%s: %s) and runs WITHOUT "
                "rematerialization" % (s, e, type(exc).__name__, exc),
                stacklevel=2)
            lower_ops(ctx, ops, s, e)
            return
        ctx.env.update(zip(produced, results))
        return
    lower_ops(ctx, ops, s, e)


def _replicate_under_mesh(*arrays):
    """Pin values to a fully-replicated sharding when tracing under an
    active MeshRunner mesh; identity otherwise (single-device traces and
    plain jit must not see mesh-less constraints)."""
    from ..parallel.api import get_active_mesh
    mesh = get_active_mesh()
    if mesh is None or mesh.size <= 1:
        return arrays if len(arrays) > 1 else arrays[0]
    from jax.sharding import NamedSharding, PartitionSpec as P
    sh = NamedSharding(mesh, P())
    out = tuple(lax.with_sharding_constraint(a, sh) for a in arrays)
    return out if len(out) > 1 else out[0]


def _vjp_with_aux(f, primal):
    out, vjp_fn, aux = jax.vjp(f, primal, has_aux=True)
    def pullback(ct):
        return vjp_fn(ct)[0]
    return (out, aux), pullback


# ---------------------------------------------------------------------------
# Program-level compilation
# ---------------------------------------------------------------------------

def analyze_state(program, fetch_names=()):
    """Statically determine which persistable vars a program reads / writes.

    Read persistables must be supplied from the Scope; written persistables
    are returned as new state (the TPU equivalent of ops mutating Variables in
    a reference Scope, framework/scope.h:48)."""
    read, written = [], []
    read_set, written_set = set(), set()

    def _persistable(block, name):
        v = block._find_var_recursive(name)
        return v is not None and v.persistable

    for block in program.blocks:
        for op in block.ops:
            names = list(op.input_arg_names)
            if op.type == 'backward':
                names += list(op.attr('wrt_names'))
            # a var written inside a control-flow sub-block is carried as
            # read-modify-write state (the untaken branch / iteration 0
            # keeps its prior value), so it counts as read too
            if block.idx != 0:
                names += list(op.output_arg_names)
            for n in names:
                if _persistable(block, n) and n not in read_set:
                    read_set.add(n)
                    read.append(n)
            for n in op.output_arg_names:
                if _persistable(block, n) and n not in written_set:
                    written_set.add(n)
                    written.append(n)
    gb = program.global_block()
    for n in fetch_names:
        if _persistable(gb, n) and n not in read_set:
            read_set.add(n)
            read.append(n)
    return read, written


def build_fn(program, fetch_names, read_names, written_names,
             static_lods=None, static_feed=None, lod_out=None,
             lower_params=None):
    """Build the raw (unjitted) whole-program function
    fn(feed, ro_state, rw_state, key) -> (fetches, new_state).

    static_lods: var name -> LoD offsets bound at compile time (feeds & state).
    static_feed: shape-bearing feed values bound as trace-time constants.
    lod_out: optional dict the trace fills with every var's produced LoD —
    read by the executor after first compile to attach LoD to fetches.
    lower_params: extra knobs op lowerings consult via ctx.params
    (e.g. loss_grad_scale)."""

    written_set = set(written_names)
    rw_names = [n for n in read_names if n in written_set]
    ro_names = [n for n in read_names if n not in written_set]

    def fn(feed, ro_state, rw_state, key):
        # a program that states its matmuls' precision is traced under it
        # (kernels and ops that state their own keep theirs)
        with jax.default_matmul_precision(program.matmul_precision) \
                if program.matmul_precision else contextlib.nullcontext():
            return body(feed, ro_state, rw_state, key)

    def body(feed, ro_state, rw_state, key):
        env = {}
        env.update(feed)
        env.update(ro_state)
        env.update(rw_state)
        ctx = LowerContext(program, program.global_block(), env, key,
                           params=lower_params,
                           lods=dict(static_lods or {}),
                           statics=dict(static_feed or {}),
                           op_offset=(lower_params or {}).get(
                               'op_offset', 0))
        lower_block(ctx)
        env = ctx.env
        if lod_out is not None:
            lod_out.clear()
            lod_out.update(ctx.lods)
        fetches = [env[n] for n in fetch_names]
        new_state = {n: env[n] for n in written_names if n in env}
        return fetches, new_state

    name_after(fn, program)
    return fn, ro_names, rw_names


def name_after(fn, program, suffix=''):
    """Name `fn` after the program it runs: jax.jit calls the XLA module
    jit_<fn.__name__>, so a device trace's 'XLA Modules' line and every
    op path read jit_lm_train, jit_lm_decode_step ... and not jit_fn."""
    fn.__name__ = fn.__qualname__ = coldstart.label_of(program) + suffix


class StateCallable(object):
    """build_fn's function, jitted ONCE over state handed in flat: `flat`
    takes (feed, ro_leaves, rw_leaves, key), the two tuples in `ro_names` /
    `rw_names` order, so a caller that keeps its state in that order
    (Executor.bind's handle) pays no sort of several hundred names a call.
    Called or lowered as fn(feed, ro_state, rw_state, key) with the two
    dicts, it lines them up and goes through the same jitted function: one
    trace and one executable a signature, whoever calls. The order is the
    SORTED one, in which jit flattens a dict: the compiled program takes
    its parameters as it did when the state went in as dicts (another
    order is another schedule: the train step read 1.6 % slower)."""

    __slots__ = ('flat', 'ro_names', 'rw_names', '_fn', '_donate',
                 '_program')

    def __init__(self, fn, ro_names, rw_names, program, donate):
        ro_names, rw_names = tuple(sorted(ro_names)), tuple(sorted(rw_names))

        def flat(feed, ro_leaves, rw_leaves, key):
            return fn(feed, dict(zip(ro_names, ro_leaves)),
                      dict(zip(rw_names, rw_leaves)), key)

        name_after(flat, program)
        self._fn = flat
        self._program = coldstart.label_of(program)
        self._donate = (2,) if donate else ()
        self.flat = jax.jit(flat, donate_argnums=self._donate)
        self.ro_names = ro_names
        self.rw_names = rw_names

    def lower_bound(self, feed, ro_leaves, rw_leaves, key, ro_formats):
        """The entry of the BOUND path (Executor.bind), lowered: `flat`
        with each read-only leaf held as `ro_formats` says, one
        `jax.experimental.layout.Format` a leaf. `Format(Layout.AUTO,
        sharding)` leaves a leaf's layout to the compiler, and the
        compiled object's `input_formats` then say how the program wants
        it held: weights never change, so whoever stages them can lay them
        out once the way the one operation that reads them takes them
        (XLA:TPU puts a transpose of the whole operand in front of a
        custom call whose parameter lies the other way round, every
        call). The feeds, the read-written leaves (donated, and rebound
        between two calls by other programs) and the key keep the default
        layout. Every argument is a `jax.ShapeDtypeStruct`: JAX lowers an
        entry that holds an AUTO from shapes alone, and what is called is
        the COMPILED object. `flat`, the entry of everyone else, is
        untouched: an entry's parameters are a schedule."""
        with coldstart.stage('trace', self._program):
            return jax.jit(
                self._fn, donate_argnums=self._donate,
                in_shardings=(None, tuple(ro_formats), None, None)
            ).lower(feed, ro_leaves, rw_leaves, key)

    def _flat_args(self, feed, ro_state, rw_state, key):
        return (feed, tuple(ro_state[n] for n in self.ro_names),
                tuple(rw_state[n] for n in self.rw_names), key)

    def __call__(self, feed, ro_state, rw_state, key):
        return self.flat(*self._flat_args(feed, ro_state, rw_state, key))

    def lower(self, feed, ro_state, rw_state, key):
        with coldstart.stage('trace', self._program):
            return self.flat.lower(
                *self._flat_args(feed, ro_state, rw_state, key))


def build_callable(program, fetch_names, read_names, written_names,
                   static_lods=None, static_feed=None, lod_out=None,
                   lower_params=None, donate=True):
    """Single-device compile of build_fn, as a `StateCallable`.

    rw_state (read-and-written persistables, e.g. params being optimized) is
    donated to XLA so parameter updates alias their input buffers — the
    equivalent of the reference's in-place optimizer kernels + memory passes
    (details/inplace_op_pass.cc), for free via buffer donation. `donate=False`
    opts out (the executor passes its policy: off under PADDLE_DONATE=0 or
    a per-call donate=False, for callers that keep stale references into
    the scope)."""
    fn, ro_names, rw_names = build_fn(program, fetch_names, read_names,
                                      written_names, static_lods=static_lods,
                                      static_feed=static_feed,
                                      lod_out=lod_out,
                                      lower_params=lower_params)
    return (StateCallable(fn, ro_names, rw_names, program, donate),
            ro_names, rw_names)
