"""Program introspection: XLA cost/memory analytics, op-level attribution
profiling, and NaN provenance.

The reference Fluid framework ships a first-class introspection tier — the
per-op profiler with sorted attribution tables (python/paddle/fluid/
profiler.py + platform/profiler.cc), the timeline exporter, and the static
``contrib.memory_usage_calc.memory_usage`` estimator. This module is its
TPU-native rebuild on top of the fingerprint compile cache (PR 1) and the
monitor substrate (PR 2), answering the three questions raw timers can't:

1. **Where do my step's FLOPs/bytes/memory go?** Every fresh executor
   compile registers its executable with this module; XLA's
   ``cost_analysis()`` (flops, transcendentals, bytes accessed) is pulled
   lazily — materialized the first time anyone looks (a ``snapshot()`` /
   ``export_prometheus()`` read, ``Executor.explain``, ``tools/
   costreport.py``, a bench row) — and exported as ``program_flops`` /
   ``program_bytes_accessed`` gauges keyed by program fingerprint.
   ``memory_analysis()`` (argument/output/temp/alias bytes -> peak) needs
   XLA buffer assignment, i.e. a SECOND compile of the same HLO, so it is
   computed on demand (``Executor.explain(memory=True)``, the default) or
   eagerly for every compile under ``PADDLE_ANALYSIS_MEMORY=1``.
   ``PADDLE_PROGRAM_ANALYTICS=0`` disables registration entirely.

2. **Which op does the time go to?** ``PADDLE_PROFILE_OPS=1`` (or the
   ``profiler.profile_ops()`` context) routes ``Executor.run`` through the
   INTERPRETING path: the program body executes eagerly, op by op, with
   per-op wall time (synced), call count, and output-bytes accounting —
   the Fluid-style sorted attribution table (``format_op_profile()``) plus
   one ``op:<type>`` span per op on the monitor ring. Ops inside a
   differentiated forward segment attribute to the ``backward`` meta op
   (they execute under jax.vjp). A profiled run recompiles nothing and
   caches nothing; it is a debugging mode, ~10-100x slower than the
   compiled path.

3. **Which op produced this NaN?** With ``PADDLE_NAN_LOCALIZE=1``, a
   FLAGS_check_nan_inf trip (or a TrainingGuard bad step) replays the
   failed step op-by-op against the PRE-RUN state and reports the FIRST op
   whose output is non-finite — op type, op index, output var, input
   stats — logged, attached to the raised error, and counted as
   ``nonfinite_localized_total{op_type}``. Programs with a ``backward`` op
   get a concrete forward scout first, so forward ops are named exactly
   even though they normally trace under jax.vjp.

Catalog + examples: docs/observability.md.
"""
import collections
import logging
import os
import threading
import time

import numpy as np

from . import monitor
from .core import lowering

__all__ = ['ProgramAnalytics', 'explain_program', 'lookup', 'records',
           'op_profile', 'format_op_profile', 'reset_op_profile',
           'profile_ops_active', 'localize_nonfinite', 'memory_usage_bytes']

logger = logging.getLogger(__name__)

# short fingerprint prefix used as the gauge label (full sha1 fingerprints
# would blow the label width for zero extra identification power in one
# process's working set)
_FP_LABEL_LEN = 12


def _env_on(name):
    return os.environ.get(name, '') not in ('', '0')


def _analytics_enabled():
    return os.environ.get('PADDLE_PROGRAM_ANALYTICS', '1') != '0'


def _aval_of(v):
    """Shape/dtype stand-in for one runtime value. Works on numpy arrays,
    live jax Arrays AND donated (deleted) ones — aval metadata survives
    donation; only the buffer is gone."""
    import jax
    dt = getattr(v, 'dtype', None)
    if dt is None:
        v = np.asarray(v)
        dt = v.dtype
    return jax.ShapeDtypeStruct(tuple(v.shape) if hasattr(v, 'shape')
                                else np.shape(v),
                                jax.dtypes.canonicalize_dtype(dt))


def _tree_avals(tree):
    if isinstance(tree, dict):
        return {k: _aval_of(v) for k, v in tree.items()}
    return _aval_of(tree)


def _aval_bytes(avals):
    total = 0
    for v in avals.values() if isinstance(avals, dict) else [avals]:
        total += int(np.prod(v.shape, dtype=np.int64)) * np.dtype(v.dtype).itemsize
    return int(total)


def _op_counts(program):
    counts = collections.Counter()
    for block in program.blocks:
        for op in block.ops:
            counts[op.type] += 1
    return dict(counts)


# ---------------------------------------------------------------------------
# compiled-program analytics registry


class ProgramAnalytics(object):
    """One compiled entry's analytics record. `cost` fields materialize on
    first read (flops/bytes from XLA HloCostAnalysis over the cached
    jaxpr — milliseconds); `memory` fields need an AOT recompile and stay
    None until someone asks (explain / PADDLE_ANALYSIS_MEMORY=1)."""

    __slots__ = ('fingerprint', 'kind', 'steps', 'donate', 'feed_batch',
                 'op_count', 'ops', 'flops', 'transcendentals',
                 'bytes_accessed', 'argument_bytes', 'output_bytes',
                 'temp_bytes', 'alias_bytes', 'peak_bytes',
                 'generated_code_bytes', '_fn', '_avals', 'created_ts')

    def __init__(self, fingerprint, kind, fn, avals, donate, steps, program):
        self.fingerprint = fingerprint
        self.kind = kind                # 'run' | 'fused' | 'explain'
        self.steps = steps              # scan iterations baked in ('fused')
        self.donate = bool(donate)
        feed = avals[0] if avals else {}
        self.feed_batch = None
        # fused entries see the STACKED feed (n_steps, batch, ...): dim 0
        # is the scan length, the batch is dim 1
        batch_dim = 1 if kind == 'fused' else 0
        for v in (feed.values() if isinstance(feed, dict) else []):
            shape = getattr(v, 'shape', None)
            if shape and len(shape) > batch_dim:
                self.feed_batch = int(shape[batch_dim])
                break
        self.op_count = sum(len(b.ops) for b in program.blocks)
        self.ops = _op_counts(program)
        self.flops = None
        self.transcendentals = None
        self.bytes_accessed = None
        self.argument_bytes = sum(_aval_bytes(a) for a in avals[:3])
        self.output_bytes = None
        self.temp_bytes = None
        self.alias_bytes = None
        self.peak_bytes = None
        self.generated_code_bytes = None
        self._fn = fn                   # dropped once fully materialized
        self._avals = avals
        self.created_ts = time.time()

    # -- materialization ---------------------------------------------------
    def _lower(self):
        # the executor's jit first call already formed this (fn, avals)
        # jaxpr — pjit caches it, so .lower() here is mlir lowering only
        # (~1 ms), not a re-trace
        return self._fn.lower(*self._avals)

    def materialize_cost(self):
        if self.flops is not None or self._fn is None:
            return self
        try:
            ca = self._lower().cost_analysis()
            d = ca[0] if isinstance(ca, (list, tuple)) else (ca or {})
            self.flops = float(d.get('flops', 0.0))
            self.transcendentals = float(d.get('transcendentals', 0.0))
            self.bytes_accessed = float(d.get('bytes accessed', 0.0))
        except Exception as e:          # noqa: BLE001 — advisory data only
            logger.warning("cost_analysis failed for %s: %s",
                           self.fingerprint[:16], e)
            self.flops = self.bytes_accessed = self.transcendentals = 0.0
            monitor.inc('analysis_error_total', labels={'stage': 'cost'})
        self._export_gauges()
        return self

    def materialize_memory(self):
        """XLA buffer-assignment memory stats: argument/output/temp/alias
        bytes and the derived peak. Costs ONE extra XLA compile of this
        program (the AOT path does not share the jit call path's
        executable cache)."""
        if self.peak_bytes is not None or self._fn is None:
            return self
        self.materialize_cost()
        try:
            with monitor.timed_span('analysis.memory',
                                    'analysis_memory_seconds'):
                ms = self._lower().compile().memory_analysis()
            if ms is not None:
                self.argument_bytes = int(ms.argument_size_in_bytes)
                self.output_bytes = int(ms.output_size_in_bytes)
                self.temp_bytes = int(ms.temp_size_in_bytes)
                self.alias_bytes = int(ms.alias_size_in_bytes)
                self.generated_code_bytes = int(
                    ms.generated_code_size_in_bytes)
                self.peak_bytes = max(
                    0, self.argument_bytes + self.output_bytes
                    + self.temp_bytes - self.alias_bytes)
                self._export_gauges()
        except Exception as e:          # noqa: BLE001 — advisory data only
            logger.warning("memory_analysis failed for %s: %s",
                           self.fingerprint[:16], e)
            monitor.inc('analysis_error_total', labels={'stage': 'memory'})
        # fully mined: release the executable/aval refs so the registry
        # never keeps an evicted compile-cache entry alive
        self._fn = None
        self._avals = None
        return self

    def _export_gauges(self):
        labels = {'fingerprint': self.fingerprint[:_FP_LABEL_LEN],
                  'kind': self.kind}
        if self.flops is not None:
            monitor.set_gauge('program_flops', self.flops, labels=labels)
            monitor.set_gauge('program_bytes_accessed', self.bytes_accessed,
                              labels=labels)
        if self.peak_bytes is not None:
            monitor.set_gauge('program_peak_bytes', self.peak_bytes,
                              labels=labels)

    def hlo_text(self):
        """Lowered HLO text of this program for post-mortem bundles
        (PADDLE_BLACKBOX_HLO=1 / tools/hlodump.py). None once the
        (fn, avals) refs were released by full materialization, or when
        lowering fails — advisory data only, never raises."""
        if self._fn is None:
            return None
        try:
            return self._lower().as_text()
        except Exception as e:          # noqa: BLE001 — advisory data only
            logger.warning("hlo_text failed for %s: %s",
                           self.fingerprint[:16], e)
            monitor.inc('analysis_error_total', labels={'stage': 'hlo'})
            return None

    # -- views -------------------------------------------------------------
    def as_dict(self):
        self.materialize_cost()
        return {
            'fingerprint': self.fingerprint,
            'kind': self.kind,
            'steps': self.steps,
            'donate': self.donate,
            'feed_batch': self.feed_batch,
            'op_count': self.op_count,
            'ops': dict(self.ops),
            'flops': self.flops,
            'transcendentals': self.transcendentals,
            'bytes_accessed': self.bytes_accessed,
            'argument_bytes': self.argument_bytes,
            'output_bytes': self.output_bytes,
            'temp_bytes': self.temp_bytes,
            'alias_bytes': self.alias_bytes,
            'peak_bytes': self.peak_bytes,
            'generated_code_bytes': self.generated_code_bytes,
        }


_reg_lock = threading.RLock()
_registry = collections.OrderedDict()   # (fingerprint, kind, sig) -> rec
_pending = []                           # records awaiting cost analysis


def _registry_cap():
    try:
        return max(1, int(os.environ.get('PADDLE_ANALYSIS_CAP', '128')))
    except ValueError:
        return 128


def _evict_over_cap():
    """LRU-evict past the cap, RELEASING the evicted records' executable/
    aval refs — the registry must not keep executables alive that the
    executor's own LRU already dropped. Callers hold _reg_lock."""
    while len(_registry) > _registry_cap():
        _, old = _registry.popitem(last=False)
        old._fn = None
        old._avals = None


def record_compiled(fn, program, args, kind='run', donate=False, steps=1):
    """Executor hook: register a freshly compiled entry for analytics.
    Cheap (aval extraction only) — the XLA analyses run lazily at first
    read. Never raises into the run path."""
    if not _analytics_enabled():
        return None
    try:
        fp = program._fingerprint()
        avals = tuple(_tree_avals(a) for a in args)
        sig = tuple(sorted((k, v.shape, str(v.dtype))
                           for k, v in avals[0].items()))
        key = (fp, kind, sig)
        with _reg_lock:
            if key in _registry:
                _registry.move_to_end(key)
                return _registry[key]
            rec = ProgramAnalytics(fp, kind, fn, avals, donate, steps,
                                   program)
            _registry[key] = rec
            _evict_over_cap()
            _pending.append(rec)
        if _env_on('PADDLE_ANALYSIS_MEMORY'):
            rec.materialize_memory()
        return rec
    except Exception as e:              # noqa: BLE001 — must not break runs
        logger.warning("analytics registration failed: %s", e)
        return None


def flush_pending():
    """Materialize cost analytics for every entry registered since the
    last flush (monitor snapshot/export call this via the pre-snapshot
    hook, so gauges are populated whenever anyone actually looks)."""
    with _reg_lock:
        todo, _pending[:] = _pending[:], []
    for rec in todo:
        rec.materialize_cost()


monitor.add_presnapshot_hook(flush_pending)


def records():
    """All registered analytics records (cost-materialized), newest last."""
    with _reg_lock:
        recs = list(_registry.values())
    return [r.materialize_cost() for r in recs]


def lookup(program_or_fp, kind=None, memory=False):
    """Newest analytics record for a program (or fingerprint string), or
    None. `memory=True` also materializes the XLA memory stats (one extra
    compile, first time only)."""
    fp = program_or_fp if isinstance(program_or_fp, str) \
        else program_or_fp._fingerprint()
    with _reg_lock:
        match = [r for (f, k, _), r in _registry.items()
                 if f == fp and (kind is None or k == kind)]
    if not match:
        return None
    rec = match[-1]
    rec.materialize_cost()
    if memory:
        rec.materialize_memory()
    return rec


def memory_usage_bytes(program):
    """Best available peak-memory estimate for `program` in BYTES, or None
    when no compiled executable has been registered/mined yet (the
    contrib.memory_usage_calc fallback path handles that case)."""
    rec = lookup(program)
    if rec is None:
        return None
    if rec.peak_bytes is None:
        rec.materialize_memory()
    return rec.peak_bytes


# ---------------------------------------------------------------------------
# Executor.explain backend


def explain_program(executor, program, feed=None, fetch_list=None,
                    scope=None, memory=True):
    """Compile-time cost/memory report for one program at one feed
    signature — without executing it. Shapes come from the feed and the
    scope's CURRENT state values (metadata only: nothing is uploaded and
    nothing runs). See Executor.explain for the public contract."""
    import jax
    from .framework import default_main_program
    from .executor import global_scope

    if program is None:
        program = default_main_program()
    program = getattr(program, '_program', program)     # CompiledProgram
    if scope is None:
        scope = global_scope()
    feed, fetch_names, static_feed, static_lods = \
        executor._prepare_run_inputs(program, feed, scope, fetch_list,
                                     count=False)
    # the key a later run() will look up (one trace, not two); a policy
    # QUERY, no run: the donation rates stand
    key = executor._entry_key(program, feed, static_lods, static_feed,
                              fetch_names, record=False)
    donate = key[-1]
    entry = executor._cache_get(key)
    if entry is None:
        # share the compile with a later run() of the same signature —
        # explain-then-train pays for one trace, not two
        entry = executor._build_entry(program, feed, fetch_names,
                                      static_lods, static_feed, donate)
        executor._cache_put(key, entry)

    feed_avals = {k: _aval_of(v) for k, v in feed.items()}
    ro_avals = {n: _aval_of(executor._state_ref(scope, n))
                for n in entry.ro_names}
    rw_avals = {n: _aval_of(executor._state_ref(scope, n))
                for n in entry.rw_names}
    key_aval = jax.ShapeDtypeStruct((2,), np.uint32)
    avals = (feed_avals, ro_avals, rw_avals, key_aval)

    fp = program._fingerprint()
    sig = tuple(sorted((k, v.shape, str(v.dtype))
                       for k, v in feed_avals.items()))
    with _reg_lock:
        rec = _registry.get((fp, 'run', sig))
        if rec is None:
            rec = ProgramAnalytics(fp, 'run', entry.fn, avals, donate, 1,
                                   program)
            _registry[(fp, 'run', sig)] = rec
            _evict_over_cap()
    rec.materialize_cost()
    if memory:
        rec.materialize_memory()
    return rec.as_dict()


# ---------------------------------------------------------------------------
# op-level attribution profiling


_profile_lock = threading.Lock()
_profile_tls = threading.local()        # profile_ops() nesting, per thread
_op_table = {}                          # op type -> stats dict
_profile_meta = {'runs': 0, 'wall_s': 0.0}


def profile_ops_active():
    """Is op-attribution mode on (PADDLE_PROFILE_OPS=1 or an open
    profiler.profile_ops() context)? Checked once per Executor.run. The
    context is THREAD-local: profiling one thread's step must not drag a
    live serving pool's runs (other threads) onto the 10-100x slower
    interpreting path, nor interleave their ops into the table — the env
    var is the explicit whole-process switch."""
    return getattr(_profile_tls, 'depth', 0) > 0 \
        or _env_on('PADDLE_PROFILE_OPS')


def push_profiling():
    _profile_tls.depth = getattr(_profile_tls, 'depth', 0) + 1


def pop_profiling():
    _profile_tls.depth = max(0, getattr(_profile_tls, 'depth', 0) - 1)


def reset_op_profile():
    with _profile_lock:
        _op_table.clear()
        _profile_meta.update(runs=0, wall_s=0.0)


def _record_op(op_type, dur_s, out_bytes):
    with _profile_lock:
        row = _op_table.get(op_type)
        if row is None:
            row = _op_table[op_type] = {
                'calls': 0, 'total_s': 0.0, 'min_s': float('inf'),
                'max_s': 0.0, 'out_bytes': 0}
        row['calls'] += 1
        row['total_s'] += dur_s
        row['min_s'] = min(row['min_s'], dur_s)
        row['max_s'] = max(row['max_s'], dur_s)
        row['out_bytes'] += out_bytes


def op_profile():
    """Attribution table: {'ops': [rows sorted by total time desc],
    'runs', 'wall_s', 'accounted_s'}. Each row: op type, calls,
    total/min/max/avg seconds, output bytes, ratio of accounted time."""
    with _profile_lock:
        rows = [dict(r, type=t) for t, r in _op_table.items()]
        meta = dict(_profile_meta)
    rows.sort(key=lambda r: -r['total_s'])
    accounted = sum(r['total_s'] for r in rows)
    for r in rows:
        r['avg_s'] = r['total_s'] / r['calls']
        r['ratio'] = r['total_s'] / accounted if accounted else 0.0
    return {'ops': rows, 'runs': meta['runs'], 'wall_s': meta['wall_s'],
            'accounted_s': accounted}


def format_op_profile(profile=None):
    """Fluid-style sorted attribution table (profiler.cc PrintProfiler)."""
    p = profile or op_profile()
    lines = [
        '------------------------->  Op Profiling Report  '
        '<-------------------------',
        'runs: %d   wall: %.3f ms   accounted: %.3f ms (%.0f%%)'
        % (p['runs'], p['wall_s'] * 1e3, p['accounted_s'] * 1e3,
           100.0 * p['accounted_s'] / p['wall_s'] if p['wall_s'] else 0.0),
        '%-24s %8s %12s %12s %12s %12s %7s' % (
            'Event', 'Calls', 'Total(ms)', 'Min(ms)', 'Max(ms)', 'Ave(ms)',
            'Ratio'),
    ]
    for r in p['ops']:
        lines.append('%-24s %8d %12.3f %12.3f %12.3f %12.3f %6.1f%%' % (
            r['type'], r['calls'], r['total_s'] * 1e3, r['min_s'] * 1e3,
            r['max_s'] * 1e3, r['avg_s'] * 1e3, r['ratio'] * 100.0))
    return '\n'.join(lines)


def _concrete_outputs(ctx, op):
    """The op's output values that are real (non-tracer) arrays right
    now — what an eager interpreting run can sync on and measure."""
    import jax
    outs = []
    for n in op.output_arg_names:
        v = ctx.env.get(n)
        if v is None or isinstance(v, jax.core.Tracer):
            continue
        vals = getattr(v, 'values', v)      # SelectedRows -> its values
        if isinstance(vals, jax.core.Tracer):
            continue
        if hasattr(vals, 'shape') and hasattr(vals, 'dtype'):
            outs.append((n, vals))
    return outs


_hook_tls = threading.local()


def _timing_hook(ctx, op, thunk):
    """Per-op timing with EXCLUSIVE (self) time: ops lowered inside
    another hooked op — the forward segment re-traced under a `backward`
    op's jax.vjp — subtract from their parent, so the table's total
    equals wall time instead of double-counting nested spans (the
    reference profiler's nested-RecordEvent accounting)."""
    import jax
    stack = getattr(_hook_tls, 'stack', None)
    if stack is None:
        stack = _hook_tls.stack = []
    with monitor.span('op:%s' % op.type):
        t0 = time.perf_counter()
        stack.append(0.0)               # accumulates child op time
        try:
            thunk()
            outs = _concrete_outputs(ctx, op)
            if outs:
                try:
                    jax.block_until_ready([v for _, v in outs])
                except Exception:       # noqa: BLE001 — host-only values
                    pass
        finally:
            child_s = stack.pop()
            dur = time.perf_counter() - t0
            if stack:
                stack[-1] += dur
    _record_op(op.type, max(0.0, dur - child_s),
               sum(int(getattr(v, 'nbytes', 0)) for _, v in outs))


def run_profiled(executor, program, feed, fetch_list, scope, return_numpy):
    """The interpreting (non-fused) executor path: build the raw program
    function and run it EAGERLY with the per-op timing hook installed.
    Honest per-op wall times (each op syncs before the next); the price is
    per-op dispatch instead of one fused XLA call. Nothing is cached —
    every profiled run re-traces, by design."""
    import jax
    from .executor import global_scope, _check_nan_inf
    from .core.selected_rows import SelectedRows
    from . import flags as _flags

    if scope is None:
        scope = global_scope()
    feed, fetch_names, static_feed, static_lods = \
        executor._prepare_run_inputs(program, feed, scope, fetch_list)
    # an entry of its own, cached nowhere, for the step's take — the
    # state as a run takes it, the run key and its counters — and its
    # fetch: what is called is the entry's function un-jitted
    entry = executor._build_entry(program, feed, fetch_names, static_lods,
                                  static_feed, False)
    rec = executor._take(scope, entry, program)
    key_arr = executor._next_key(program)
    monitor.inc('op_profile_run_total')
    t0 = time.perf_counter()
    with monitor.span('profile_ops'):
        with lowering.op_hook(_timing_hook):
            fetches, new_state = entry.fn._fn(feed, rec.ro, rec.rw, key_arr)
        jax.block_until_ready([v for v in new_state.values()
                               if not isinstance(v, SelectedRows)])
    wall = time.perf_counter() - t0
    with _profile_lock:
        _profile_meta['runs'] += 1
        _profile_meta['wall_s'] += wall

    scope.update(new_state)
    if _flags.get_flags('check_nan_inf'):
        _check_nan_inf(new_state, dict(zip(fetch_names, fetches)))
    for n in entry.written:
        lod = entry.lod_out.get(n)
        if lod:
            scope._lods[n] = lod
        else:
            scope._lods.pop(n, None)
    return executor._fetch(
        entry, [f.to_dense() if isinstance(f, SelectedRows) else f
                for f in fetches], return_numpy)


# ---------------------------------------------------------------------------
# NaN provenance


def nan_localization_enabled():
    return _env_on('PADDLE_NAN_LOCALIZE')


class _LocalizedNonFinite(Exception):
    def __init__(self, info):
        Exception.__init__(self, info['op_type'])
        self.info = info


def _value_stats(v):
    try:
        vals = getattr(v, 'values', v)
        arr = np.asarray(vals)
    except Exception:                   # noqa: BLE001 — diagnostics only
        return {'repr': type(v).__name__}
    out = {'shape': list(arr.shape), 'dtype': str(arr.dtype)}
    if arr.size and arr.dtype.kind == 'f':
        finite = np.isfinite(arr)
        out['finite_frac'] = round(float(finite.mean()), 6)
        if finite.any():
            fa = arr[finite]
            out['min'] = float(fa.min())
            out['max'] = float(fa.max())
            out['absmean'] = float(np.abs(fa).mean())
    return out


def _check_hook(ctx, op, thunk):
    thunk()
    bad = []
    for n, v in _concrete_outputs(ctx, op):
        arr = np.asarray(v)
        if arr.dtype.kind == 'f' and not np.isfinite(arr).all():
            bad.append(n)
    if bad:
        inputs = {n: _value_stats(ctx.env[n])
                  for n in op.input_arg_names if ctx.has(n)}
        outputs = {n: _value_stats(ctx.env[n]) for n in bad}
        raise _LocalizedNonFinite({
            'op_type': op.type, 'op_index': ctx.op_index,
            'bad_outputs': bad, 'output_stats': outputs,
            'input_stats': inputs})


def _localize_core(program, feed, ro, rw, key_arr, static_lods,
                   static_feed):
    """Replay one step op-by-op against its pre-run inputs; return the
    info dict of the FIRST op producing a non-finite output, or None when
    the replay comes back clean (e.g. a flaky hardware bit flip)."""
    from .framework import Program  # noqa: F401 — doc anchor

    gb = program.global_block()
    ops = gb.ops
    b = next((i for i, op in enumerate(ops) if op.type == 'backward'), None)

    def _ro_rw_env():
        env = {}
        env.update(feed)
        env.update(ro)
        env.update(rw)
        return env

    # Pass A — concrete forward scout: ops before the first `backward`
    # run fully eagerly (identical math + identical per-op RNG folds), so
    # a forward culprit is named exactly even though the real run traced
    # these ops under jax.vjp.
    scout_hi = b if b is not None else len(ops)
    if scout_hi:
        ctx = lowering.LowerContext(program, gb, _ro_rw_env(), key_arr,
                                    lods=dict(static_lods or {}),
                                    statics=dict(static_feed or {}))
        try:
            with lowering.op_hook(_check_hook):
                lowering.lower_ops(ctx, ops, 0, scout_hi)
        except _LocalizedNonFinite as e:
            return e.info

    if b is None:
        return None

    # Pass B — full replay: the forward is finite, so the culprit is the
    # backward (gradients) or an op after it (optimizer update). Those
    # all see concrete values in the eager interpretation, so the hook
    # names them exactly; non-finite GRADIENTS attribute to `backward`.
    _, written = lowering.analyze_state(program, [])
    fn, _, _ = lowering.build_fn(program, [], list(ro) + list(rw), written,
                                 static_lods=static_lods,
                                 static_feed=static_feed)
    try:
        with lowering.op_hook(_check_hook):
            fn(feed, ro, rw, key_arr)
    except _LocalizedNonFinite as e:
        return e.info
    return None


def localize_nonfinite(program, feed, ro_state, rw_state, key_arr,
                       static_lods=None, static_feed=None):
    """Opt-in NaN/Inf localization (PADDLE_NAN_LOCALIZE=1): see module
    docstring. Returns the culprit info dict or None; never raises — a
    broken replay must not mask the original non-finite error."""
    if not nan_localization_enabled():
        return None
    try:
        with monitor.timed_span('nan_localize', 'nan_localize_seconds'):
            info = _localize_core(program, feed, ro_state, rw_state,
                                  key_arr, static_lods, static_feed)
    except Exception as e:              # noqa: BLE001 — diagnostics only
        logger.warning("NaN localization replay failed: %s", e)
        monitor.inc('analysis_error_total', labels={'stage': 'localize'})
        return None
    if info is not None:
        monitor.inc('nonfinite_localized_total',
                    labels={'op_type': info['op_type']})
        logger.error(
            "non-finite value localized to op #%d (%s): outputs %s; "
            "input stats: %s", info['op_index'], info['op_type'],
            info['bad_outputs'], info['input_stats'])
    return info


def localize_from_scope(executor, program, feed, scope, key_arr):
    """TrainingGuard entry point: localize against a ROLLED-BACK scope
    (the pre-step state the guard restored) using the failed step's RNG
    key. Returns the culprit info dict or None."""
    if not nan_localization_enabled():
        return None
    try:
        feed, _, static_feed, static_lods = \
            executor._prepare_run_inputs(program, feed, scope, [],
                                         count=False)
        # the state as the failed step took it (Executor._take)
        from .executor import _by_name
        entry = executor._build_entry(program, feed, [], static_lods,
                                      static_feed, False)
        rec = executor._take(scope, entry, program)
        ro, rw = _by_name(entry.fn, rec.ro, rec.rw)
        if key_arr is None:
            import jax
            key_arr = jax.random.PRNGKey(0)
    except Exception as e:              # noqa: BLE001 — diagnostics only
        logger.warning("NaN localization setup failed: %s", e)
        return None
    return localize_nonfinite(program, feed, ro, rw, key_arr,
                              static_lods, static_feed)


def format_localization(info):
    """One-line human rendering of a localize_nonfinite() result."""
    if not info:
        return 'no op localized (replay was finite)'
    return ('first non-finite output produced by op #%d type=%r '
            'outputs=%s inputs=%s'
            % (info['op_index'], info['op_type'], info['bad_outputs'],
               sorted(info['input_stats'])))
