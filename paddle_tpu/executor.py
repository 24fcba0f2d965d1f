"""Executor + Scope.

Capability parity with reference python/paddle/fluid/executor.py (Executor:262,
run:451, global_scope:34) and the C++ serial executor it drives
(framework/executor.cc:185). TPU-native redesign:

- `Executor.run(program, feed, fetch_list)` compiles the whole program once per
  (program version, feed signature, fetch list) into a single XLA executable
  (program cache ≈ reference executor.py:224 _get_program_cache_key), then
  repeatedly calls it. There is no per-op interpreter.
- The Scope is a flat name -> array store holding persistable state (params,
  optimizer moments, LR counters). It is the checkpointable pytree: the
  reference's "everything persistable is the checkpoint" principle. Scope
  values are DEVICE-RESIDENT jax.Arrays across run() calls: state is uploaded
  once, updates land as the jitted outputs, and host materialization happens
  only at explicit read points (fetch with return_numpy=True, tensor shims,
  io.save_persistables). The rw-state pytree is donated by default so updates
  alias their input buffers (see _donation_enabled for the escape hatches).
- feed: numpy (or already-device jax.Array) in; fetch: numpy out by default
  (the reference's feed/fetch ops collapse into function arguments/results);
  return_numpy=False keeps fetches on device.
- Compiled entries are cached by structural program fingerprint (not object
  identity) in per-executor + process-wide LRU caches, and XLA's persistent
  compilation cache is wired for cross-process reuse — see
  docs/executor_performance.md for the full contract.
"""
import collections
import contextlib
import copy
import functools
import operator
import os
import threading
import time
import types
import weakref

import numpy as np
import jax
import jax.numpy as jnp

from . import analysis
from . import blackbox
from . import coldstart
from . import goodput
from . import monitor
from . import resilience
from . import trace as trace_mod
from .framework import (Program, Variable, default_main_program, CPUPlace,
                        TPUPlace)
from .core import lowering
from .core.lod import normalize_lod
from .core.registry import get_op, has_op
from .core.types import convert_np_dtype_to_dtype_

__all__ = ['Executor', 'Scope', 'BoundProgram', 'StepFuture',
           'global_scope', 'scope_guard']


class _TensorShim(object):
    """Minimal LoDTensor-like view over a scope entry (numpy conversion +
    set()), so reference-style `scope.find_var(n).get_tensor()` code works."""

    def __init__(self, scope, name):
        self._scope = scope
        self._name = name

    def __array__(self, dtype=None):
        arr = np.asarray(self._scope._vars[self._name])
        return arr.astype(dtype) if dtype is not None else arr

    def shape(self):
        return list(np.shape(self._scope._vars[self._name]))

    def set(self, value, place=None):
        self._scope.set(self._name, np.asarray(value))

    def set_lod(self, lod):
        self._scope._lods[self._name] = lod

    def lod(self):
        return self._scope._lods.get(self._name, [])


class _VarShim(object):
    def __init__(self, scope, name):
        self._scope = scope
        self._name = name

    def get_tensor(self):
        return _TensorShim(self._scope, self._name)


class _Held(object):
    """What an entry's last call on a scope left for its next to take
    without looking (`Scope._held`, one a compiled entry, gone with the
    entry): `ro`, the read-only leaves in the entry's order and as it
    wants them placed, good while `gen` is the scope's count of writes
    to a staged name; `rw`, the read-written leaves it returned — the
    very arrays the scope holds — good while `writes` is the scope's
    count of all writes. `gen` is None where the scope does not hold
    every read-only leaf (a host value converted for one call alone):
    such a record is never kept."""

    __slots__ = ('ro', 'gen', 'rw', 'writes')

    def __init__(self, ro, gen, rw):
        self.ro, self.gen, self.rw, self.writes = ro, gen, rw, None


class Scope(object):
    """Flat variable store (reference framework/scope.h:48, minus the parent
    chain — sub-scopes are an interpreter artifact; XLA keeps intermediates
    in registers/HBM)."""

    def __init__(self):
        self._vars = {}
        self._lods = {}
        # the records (`_Held`), written by a call's commit and read by
        # the next one's take (Executor._take / _commit), and the two
        # counts they are good by. `_staged`: the names some record
        # keeps as read-only state, each with the layout a bound
        # program's compiled entry chose for it (a `Format`; None where
        # the backend offers none, or nobody asked): a program bound
        # later takes the leaf as it lies. Every write goes through
        # set / update / drop — nothing else touches `_vars` — and one
        # to a name nobody staged (the KV pools, a training run's
        # parameters) costs a set lookup.
        self._held = weakref.WeakKeyDictionary()
        self._staged = {}
        self._gen = 0
        self._writes = 0
        # the one record whose read-written leaves stand: an entry's own
        # rebind is a write, so no two do
        self._fresh = None

    # dict-ish API used internally
    def get(self, name, default=None):
        return self._vars.get(name, default)

    def set(self, name, value):
        self._vars[name] = value
        self._wrote(name in self._staged)

    def update(self, d):
        if d:
            self._vars.update(d)
            # two key views: the shorter one is walked
            self._wrote(not self._staged.keys().isdisjoint(d.keys()))

    def drop(self, name):
        self._vars.pop(name, None)
        self._wrote(name in self._staged)

    def _wrote(self, staged):
        """A write has landed, on a staged name or on none."""
        self._writes += 1
        self._gen += staged
        self._let_go(staged)

    def _let_go(self, every):
        """The writer lets the records go as it moves the counts — each
        is stale by then — so that an array it replaced or dropped is
        held by nothing: the read-written leaves of the one record that
        has any, and after a write to a staged name `every` record."""
        fresh, self._fresh = self._fresh, None
        if fresh is not None:
            fresh.rw = None
        if every:
            self._held.clear()

    def _keep(self, entry, rec):
        self._held[entry] = self._fresh = rec

    def _relay(self, name, value):
        """Hold `name` as `value`, the SAME logical array laid out another
        way on the device (BoundProgram._place): no write, no count — but
        no record may keep the array as it lay."""
        self._vars[name] = value
        self._let_go(True)

    def has(self, name):
        return name in self._vars

    def names(self):
        return sorted(self._vars)

    # fluid-style API
    def find_var(self, name):
        if name not in self._vars:
            return None
        return _VarShim(self, name)

    def var(self, name):
        self._vars.setdefault(name, None)
        return _VarShim(self, name)

    def new_scope(self):
        return Scope()


def _check_nan_inf(new_state, fetches, host=np.asarray):
    """FLAGS_check_nan_inf: scan run outputs for NaN/Inf and raise naming
    the variable (reference framework/operator.cc:973 checks every op
    output; whole-program XLA means we check at the program boundary —
    use FLAGS_debug_nans to trap at the producing op instead). `host`:
    how the entry's values come to the host (`_CompiledEntry.to_host`)."""
    from .core.selected_rows import SelectedRows
    bad = []
    for group in (new_state, fetches):
        for name, v in group.items():
            if isinstance(v, SelectedRows):
                v = v.values
            arr = host(v)
            if arr.dtype.kind == 'f' and not np.isfinite(arr).all():
                bad.append(name)
    if bad:
        monitor.inc('nan_check_trigger_total')
        raise RuntimeError(
            "FLAGS_check_nan_inf: NaN/Inf detected in %s after executor "
            "run" % sorted(set(bad)))


def _feed_from_spec(feed_spec):
    """Normalize a precompile/warmfarm feed spec into concrete arrays:
    real arrays/scalars pass through; (shape, dtype) tuples and
    ShapeDtypeStruct-likes become zero arrays. ONE implementation shared
    by Executor.precompile and warmfarm.signature so the two can never
    disagree on what a spec hashes to."""
    def _dtype_like(v):
        try:
            np.dtype(v)
            return True
        except TypeError:
            return False

    feed = {}
    for name, spec in (feed_spec or {}).items():
        if isinstance(spec, (np.ndarray, jax.Array)) or np.isscalar(spec):
            feed[name] = spec
        elif isinstance(spec, (tuple, list)) and len(spec) == 2 and \
                not hasattr(spec, 'dtype') and _dtype_like(spec[1]):
            # (shape, dtype) — the dtype-like check keeps a 2-element
            # DATA list ([1.0, 2.0]) on the array path below
            feed[name] = np.zeros(spec[0], dtype=spec[1])
        elif hasattr(spec, 'shape') and hasattr(spec, 'dtype'):
            # jax.ShapeDtypeStruct or anything aval-like
            feed[name] = np.zeros(spec.shape, dtype=spec.dtype)
        else:
            feed[name] = np.asarray(spec)      # plain lists: real data
    return feed


def _goodput_leaf(new_state, fetches):
    """First device array among a dispatch's outputs — what the goodput
    completer blocks on for honest device-completion time. One stream
    orders everything, so any output leaf marks the step done."""
    for v in new_state.values():
        if isinstance(v, jax.Array):
            return v
    for v in fetches:
        if isinstance(v, jax.Array):
            return v
    return None


def _run_key(random_seed, program_runs, global_counter):
    """PRNG base key for one executor run.

    Seeded program: key = f(seed, per-program run index) — deterministic
    across executors/scopes (reference: fixed-seed programs reproduce init
    exactly), while dropout still varies step to step. The run index lives
    on the Program (not the compile-cache entry) so cache misses or
    alternating fetch lists never restart the stream.
    Unseeded: fresh key per run."""
    if random_seed:
        return _seeded_key(random_seed, program_runs)
    return jax.random.PRNGKey(global_counter % (2 ** 31))


@functools.partial(jax.jit, static_argnums=0)
def _seeded_key(seed, run):
    """fold_in(PRNGKey(seed), run) as ONE compiled call a run, `run` a
    host scalar: eagerly it is seven primitive binds, each a dispatch."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), run)


def _by_name(fn, ro, rw):
    """The state a `StateCallable` takes flat, as the two dicts by name."""
    return dict(zip(fn.ro_names, ro)), dict(zip(fn.rw_names, rw))


def _next_program_run(program):
    n = getattr(program, '_rng_run_counter', 0) + 1
    program._rng_run_counter = n
    return n


def _op_needs_rng(opdef, op):
    """An OpDef's needs_rng is a bool for most ops, or a static predicate
    over the op instance (attrs only — resolvable at bind time) for ops
    whose RNG use is conditional, like fused_ffn_tail's train-mode-only
    dropout key."""
    nr = opdef.needs_rng
    return nr(op) if callable(nr) else bool(nr)


# Ops whose lowering calls back into the host (pure_callback / io_callback /
# debug.print). By default they compile into the program as callbacks.
# Under PADDLE_SEGMENT_HOST_OPS=1 a program containing them executes in
# SEGMENTS instead: compiled device segments split at each host op, with the
# host op run eagerly on CPU between them and only the crossing vars
# transferred — the TPU-native analog of the reference's per-op kernel
# fallback + cross-place PrepareData (framework/operator.cc:930,1003), done
# at program granularity because XLA compiles whole programs, not single ops.
_HOST_SEGMENT_OPS = ('py_func', 'print', 'detection_map', 'save',
                     'save_combine')


def _donation_enabled(override=None, record=True):
    """Default-ON buffer donation for the rw-state pytree: parameter updates
    alias their input buffers instead of holding old+new state simultaneously
    (2x peak HBM). Escape hatches: a per-call ``donate=`` override on
    Executor.run / run_fused (`override` here) wins over everything except
    optest collection — TrainingGuard's rollback snapshot and the serving
    pool's cached params both need donation off for ONE call without
    touching any other thread's runs; PADDLE_DONATE=0 disables both run
    paths process-wide — callers that keep reading a stale reference to a
    pre-run scope value need it (the scope itself is always rebound to the
    new state right after the call, so normal callers never observe a
    donated buffer). Guard: optest collection records the pre-run rw state
    after the call, which donation would have deleted.

    Every resolution is counted: donation_run_total when ON,
    donation_fallback_total{reason} when OFF — so "did this run donate"
    is a snapshot read, not a debugger session."""
    def _count(name, labels=None):
        # record=False: a policy QUERY (Executor.explain resolving the
        # donation default for its cache key), not a run — must not move
        # the donation run/fallback rates
        if record:
            monitor.inc(name, labels=labels)

    if os.environ.get('PADDLE_OPTEST_COLLECT_DIR'):
        _count('donation_fallback_total',
               labels={'reason': 'optest_collect'})
        return False
    if isinstance(override, str):
        # a named forced fallback — run_async passes 'inflight' when the
        # resolved default WOULD have donated: under overlapped execution
        # a donated buffer could still be referenced by an earlier
        # in-flight step's un-materialized results, so donation is forced
        # off and the reason recorded (the pay-for-overlap HBM tradeoff,
        # docs/executor_performance.md)
        _count('donation_fallback_total', labels={'reason': override})
        return False
    if override is not None:
        if override:
            _count('donation_run_total')
            return True
        _count('donation_fallback_total',
               labels={'reason': 'per_call_opt_out'})
        return False
    env = os.environ.get('PADDLE_DONATE')
    if env is not None:
        if env != '0':
            _count('donation_run_total')
            return True
        _count('donation_fallback_total',
               labels={'reason': 'env_opt_out'})
        return False
    _count('donation_run_total')
    return True


# Where the on-disk XLA cache goes when JAX_COMPILATION_CACHE_DIR does not
# say: a fixed directory inside the checkout, resolved from this package's
# own location. The path is part of the cache's key, so it must not move
# with $HOME, a pid or a timestamp — a directory that moves never hits.
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    '.jax_cache')

_persistent_cache_dir = [None]


def _wire_persistent_cache():
    """Make sure JAX's persistent compilation cache is in force before a
    compile, so a SECOND PROCESS compiling the same program hits the
    on-disk XLA cache and time-to-first-step drops from compile_s to
    cache-deserialize time. One rule: where JAX_COMPILATION_CACHE_DIR (or
    jax.config) already names a directory, JAX uses it and this sets
    nothing; otherwise, on an accelerator backend, the fixed in-checkout
    directory `_CHECKOUT_CACHE_DIR`, with the min-compile-time /
    min-entry-size floors zeroed so every executor program is eligible.
    The CPU backend gets no default cache: its compiles are cheap, and
    tier-1 must not litter the checkout. Called by every compile site —
    the serial Executor and both SPMD runners. Returns the directory in
    force ('' for none) and mirrors it in the
    compile_persistent_cache_wired gauge."""
    if _persistent_cache_dir[0] is None:
        # ahead of the first compile: from here on JAX's own durations
        # split each set-up frame into its stages
        coldstart.listen()
        path = jax.config.jax_compilation_cache_dir or ''
        if not path and jax.default_backend() != 'cpu':
            try:
                os.makedirs(_CHECKOUT_CACHE_DIR, exist_ok=True)
            except OSError as e:    # read-only install: say so, run cold
                import warnings
                warnings.warn(
                    "no persistent compile cache: cannot create %s (%s) — "
                    "every process will compile from scratch; set "
                    "JAX_COMPILATION_CACHE_DIR to a writable directory"
                    % (_CHECKOUT_CACHE_DIR, e), RuntimeWarning,
                    stacklevel=2)
            else:
                path = _CHECKOUT_CACHE_DIR
                jax.config.update('jax_compilation_cache_dir', path)
                jax.config.update(
                    'jax_persistent_cache_min_compile_time_secs', 0)
                jax.config.update(
                    'jax_persistent_cache_min_entry_size_bytes', -1)
        _persistent_cache_dir[0] = path
    monitor.set_gauge('compile_persistent_cache_wired',
                      1.0 if _persistent_cache_dir[0] else 0.0)
    return _persistent_cache_dir[0]


class _LRUCache(object):
    """Bounded compile cache: long-lived serving processes must not leak
    compiled entries (and the strong program refs they hold) without bound.
    Hits move the key to the back; inserting past the cap evicts from the
    front (least recently used). Exposes the small dict surface the
    tools/tests already use (len, iter, items, get, [k]=v, clear)."""

    def __init__(self, cap=None):
        # cap=None resolves PADDLE_EXECUTOR_CACHE_SIZE lazily at each bound
        # check, so the env var works even when set after import (the
        # module-level _shared_cache is constructed at import time)
        self._cap = max(1, int(cap)) if cap is not None else None
        self._d = collections.OrderedDict()
        # the process-wide cache is shared by every Executor; serving
        # processes run one executor per thread, so all ops take the lock
        # (iteration hands out snapshots rather than live iterators)
        self._lock = threading.RLock()

    @property
    def cap(self):
        if self._cap is not None:
            return self._cap
        try:
            return max(1, int(os.environ.get('PADDLE_EXECUTOR_CACHE_SIZE',
                                             '64')))
        except ValueError:
            return 64

    def get(self, key, default=None):
        with self._lock:
            try:
                self._d.move_to_end(key)
            except KeyError:
                return default
            return self._d[key]

    def __setitem__(self, key, value):
        with self._lock:
            self._d[key] = value
            self._d.move_to_end(key)
            while len(self._d) > self.cap:
                self._d.popitem(last=False)
                monitor.inc('compile_cache_eviction')

    def __contains__(self, key):
        with self._lock:
            return key in self._d

    def __len__(self):
        with self._lock:
            return len(self._d)

    def __iter__(self):
        with self._lock:
            return iter(list(self._d))

    def items(self):
        with self._lock:
            return list(self._d.items())

    def clear(self):
        with self._lock:
            self._d.clear()


# Process-wide compiled-entry cache, keyed by program FINGERPRINT (structural
# identity, framework.Program._fingerprint) rather than _uid: a re-built but
# identical Program — a fresh Predictor on the same saved model, a rebuilt
# graph in a new Executor — reuses the compiled entry instead of recompiling.
# Per-executor caches front this one so Executor.close() / per-executor
# bookkeeping keep their existing semantics.
_shared_cache = _LRUCache()


_global_scope = Scope()
_scope_stack = [_global_scope]


def global_scope():
    return _scope_stack[-1]


class scope_guard(object):
    def __init__(self, scope):
        self._scope = scope

    def __enter__(self):
        _scope_stack.append(self._scope)

    def __exit__(self, *a):
        _scope_stack.pop()


def _run_phase(name):
    """Phase `name` of a run (monitor.phase; `Executor._step`): its self
    time into executor_run_phase_seconds_total{phase=name}, and a
    'paddle_tpu:run.<name>' span in a profiler session. prepare: feed
    preparation, the key, the cache lookup, `_take`, the run key, the
    flight recorder's step note; dispatch: `_call`; commit: `_commit`;
    fetch: `_fetch`, the wait for the device; compile: lowering and the
    first call of a new signature (the self time of set-up's frames
    there, `_compile_frame`); segmented: a PADDLE_SEGMENT_HOST_OPS run."""
    return monitor.phase('run.' + name, 'executor_run_phase_seconds_total',
                         {'phase': name})


def _no_phase(name):
    return contextlib.nullcontext()


# a set-up frame inside Executor.run is the run's `compile` phase as well
_RUN_COMPILE = ('executor_run_phase_seconds_total', {'phase': 'compile'})


def _compile_frame(program, stage='first_run', since=None):
    return coldstart.compile_frame(program, stage, since, *_RUN_COMPILE)


def _first_call(frame, entry, feed, rec, key):
    """The first call of a newly made entry inside `frame`
    (coldstart.compile_frame): jax.jit is lazy, the XLA compile
    happens inside it, so honest compile wall time spans lowering + that
    call — its dispatch: the execution runs on behind what the caller
    does next, as it did before there was a frame (waited for, it cost
    the train cell 1.2 s of every start). A transient XLA failure
    (RESOURCE_EXHAUSTED) retries under the 'compile' site's policy."""
    def attempt():
        return entry.call(feed, rec.ro, rec.rw, key)
    with frame:
        try:
            return attempt()
        except Exception as e:          # noqa: BLE001 — classified inside
            return entry.retry(e, attempt, site='compile',
                               state=dict(zip(entry.rw_names, rec.rw)))


def _notify_dirs(program):
    """The directories `program`'s checkpoint_notify ops name."""
    return [op.attr('dir', '') or 'checkpoint_notify'
            for op in program.global_block().ops
            if op.type == 'checkpoint_notify']


def _as_tuple(scope, names, leaves, program):
    """Where a plain entry wants a leaf the walk found: where it is."""
    return tuple(leaves)


def _keep_nothing(scope, entry, rec):
    pass


def _raise(e, *args, **kwargs):
    raise e


class _CompiledEntry(object):
    """One compiled call on a scope, whoever makes it — `Executor.run`,
    `bind`'s handle, `run_fused`, a segment of a host-op program, the
    SPMD runners: `fn`, the `lowering.StateCallable`, and its names; what
    the program said when it was lowered (`fetch_names`, `written`,
    `lod_out`, `notify_dirs`); and what differs between the ways of
    running it, set where the entry is built and never asked about by
    the step (Executor._take / _call / _commit):

    `kind`, `steps`  what a dispatch is booked to goodput as.
    `call`     what is called, (feed, ro leaves, rw leaves, key): `fn.flat`;
               a handle's own executable; a sharded `flat` in its mesh.
    `place`    how leaves the walk found are put where the entry wants
               them, (scope, names, leaves, program) -> tuple: as they
               are; onto the mesh (`spmd.place_state`); a handle's
               weights in the layouts its executable chose.
    `retry`    what a failed call is handed to: `resilience.retry_after`
               with the donated-buffer guard; across processes, nothing.
    `keep`     where commit leaves the record for the next take
               (`Scope._keep`); across processes, nowhere.
    `to_host`  how a fetch comes to the host.
    `feed_shardings`, `state_shardings`  a sharded entry's, by name."""

    # holds a strong ref to the program so id(program) cache keys can never
    # alias a garbage-collected program's address
    __slots__ = ('fn', 'fetch_names', 'ro_names', 'rw_names', 'written',
                 'program', 'lod_out', 'notify_dirs', 'bound', 'fp', 'kind',
                 'steps', 'call', 'place', 'retry', 'keep', 'to_host',
                 'feed_shardings', 'state_shardings', '__weakref__')

    def __init__(self, fn, fetch_names, written, program, lod_out=None,
                 kind='run', steps=1, fp=None, call=None, place=_as_tuple,
                 retry=resilience.retry_after, keep=Scope._keep,
                 to_host=np.asarray, feed_shardings=None,
                 state_shardings=None):
        self.fn = fn
        self.fetch_names = fetch_names
        self.ro_names, self.rw_names = fn.ro_names, fn.rw_names
        self.written = written
        self.program = program
        self.lod_out = lod_out if lod_out is not None else {}
        self.fp = fp or program._fingerprint()
        self.kind, self.steps = kind, steps
        self.call = call if call is not None else fn.flat
        self.place, self.retry, self.keep = place, retry, keep
        self.to_host = to_host
        self.feed_shardings = feed_shardings
        self.state_shardings = state_shardings
        # BoundProgram._compile's executables, by the formats they were
        # asked for: a second engine on a fresh scope compiles nothing
        self.bound = {}
        # precomputed once per compile so the hot run path doesn't rescan
        # the op list every call
        self.notify_dirs = _notify_dirs(program)


class FetchedTensor(np.ndarray):
    """Numpy array + LoD — what fetch returns for ragged results (the
    LoDTensor view the reference's as_numpy path loses, executor.py:72)."""

    def lod(self):
        return [list(l) for l in getattr(self, '_lod', ())]

    def recursive_sequence_lengths(self):
        from .core.lod import lengths_from_offsets
        return [list(lengths_from_offsets(l))
                for l in getattr(self, '_lod', ())]


def _fetched(arr, lod):
    out = np.asarray(arr).view(FetchedTensor)
    out._lod = normalize_lod(lod)
    return out


class _DeferredFetch(object):
    """A LoD-carrying fetch whose `_fetched` wrap is postponed to
    `StepFuture` materialization: wrapping at dispatch time would
    np.asarray — and so block on — the still-running async step."""

    __slots__ = ('arr', 'lod')

    def __init__(self, arr, lod):
        self.arr = arr
        self.lod = lod


def _layouts_offered():
    """Whether the backend lets a compiled entry say how its parameters
    lie. The CPU's compiler answers every AUTO with the default: there a
    bound entry is the jitted `flat`, lowered text byte for byte."""
    return jax.default_backend() != 'cpu'


def _open_format(leaf):
    """How a read-only leaf that no bound program of its scope has staged
    yet may lie: the compiler chooses."""
    from jax.experimental.layout import Format, Layout
    return Format(Layout.AUTO, leaf.sharding)


@contextlib.contextmanager
def _compiled_here():
    """JAX's persistent compile cache out of force for the compiles
    inside: an executable that cache hands back gives its RESULT in the
    default layout whatever it was compiled for (JAX 0.9.0; the layouts of
    ARGUMENTS survive, which is all a bound entry asks for), so the small
    program `jax.device_put` runs to lay a leaf out anew has to be
    compiled by the process that uses it — or a second process' relaid
    leaf lies as it did."""
    from jax.experimental.compilation_cache import compilation_cache
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update('jax_enable_compilation_cache', cached)
        compilation_cache.reset_cache()


def _spec(value):
    """`value`'s shape, dtype (as jit takes it) and sharding, to lower from."""
    aval = jax.typeof(value)
    return jax.ShapeDtypeStruct(aval.shape, aval.dtype,
                                sharding=getattr(value, 'sharding', None))


class BoundProgram(object):
    """A fixed-signature dispatch handle from `Executor.bind`: a call is
    the executor's step bare (`_take`, `_call`, `_commit`) on the handle's
    own entry — no cache-key hashing, no feed re-preparation, no span or
    phase machinery: the per-token host tax of a decode loop.

    The READ-ONLY state (a decode step's weights: several hundred names
    that never change) is the scope's record's (`_Held.ro`), walked again
    only after a write to one of its names: `Scope` counts those
    (`Scope._gen`), a call compares one integer. A weight rebound with
    `scope.set` or a tensor shim's `set` is what the next call uses,
    exactly as if every call walked; `executor_bound_restage_total`
    counts the walks after the first. The READ-WRITTEN state (the KV
    pools) is the record's too while the handle's own rebind is the
    scope's last write, and comes from the scope otherwise: other
    programs rebind the same names between two calls. The walk is
    `Executor._state_value`: the not-initialised error, the one lossless
    upload of a host-written value and the freeze of its buffer. A host
    value that cannot be cached in the scope (a dtype jax narrows, a
    view) is converted again every call, as `run()` does.

    Staged state lies as the compiled entry wants it. Where the backend
    offers layouts (`_layouts_offered`), the handle's entry is compiled
    with every read-only leaf's layout left to the compiler
    (`StateCallable.lower_bound`), and the entry's `place` puts each leaf
    in the format the executable asks for: one `jax.device_put` for a
    leaf that lies otherwise (`executor_bound_relayout_total`), nothing
    for the others. The relaid array REPLACES the scope's value under its
    name — the same shape and values, one copy in HBM, no write counted —
    and `Scope._staged` keeps the format: a program bound later on the
    scope compiles for the layout it finds and asks nothing again, so a
    leaf is relaid once a scope and every handle's entry agrees with what
    the scope holds. `run()`, `precompile` and the runners keep the
    default entry (jit compiles for the layout of a committed argument,
    as these are).

    FLAGS_check_nan_inf raises at the program boundary as in run() (the
    op-level localization replay stays a run() feature). Calls are NOT
    thread-safe against each other (the decode loop owns its engine's
    executor thread)."""

    __slots__ = ('_exe', '_entry', '_program', '_scope', '_needs_rng',
                 '_key0', '_ro', '_flat', '_formats', 'restages',
                 'relayouts', 'first_out', 'fetch_names', 'example_feed')

    def __init__(self, exe, entry, program, scope, needs_rng, example_feed):
        self._exe, self._program, self._scope = exe, program, scope
        self._needs_rng = needs_rng
        # RNG-free programs reuse one key — building a PRNGKey is itself
        # a device dispatch, pure waste for is_test decode steps
        self._key0 = jax.random.PRNGKey(program.random_seed or 0)
        # what bind's own run fetched (Executor.bind sets it), and the
        # read-only leaves it took: a call that takes others has restaged
        self.first_out = self._ro = None
        self.fetch_names = tuple(entry.fetch_names)
        # the PREPARED bind-time feed (LoD tuples flattened, dtypes
        # normalized): callers that dispatch a constant feed every call —
        # bench timing loops — pass it back verbatim instead of
        # re-preparing per call
        self.example_feed = example_feed
        # how often a call found the scope written and staged again, and
        # how many leaves staging has laid out anew
        self.restages = self.relayouts = 0
        self._flat, self._formats = entry.fn.flat, None
        if _layouts_offered():
            self._compile(entry)
        # the handle's own entry: the one `run()` looked up or built,
        # called the handle's way, with a record of its own (the leaves
        # as THIS executable wants them)
        self._entry = own = copy.copy(entry)
        own.call = self._flat
        if self._formats is not None:
            own.place = self._place
        names = entry.ro_names
        scope._staged.update(zip(names, self._formats or [None] * len(names)))

    def _compile(self, entry):
        """The entry compiled for layouts: each read-only leaf as an
        earlier handle of this scope had it laid (`Scope._staged`), the
        compiler's choice for the others; `_formats` is what the
        executable asks for, a leaf."""
        scope, fn, program = self._scope, entry.fn, self._program
        fixed = tuple([scope._staged.get(n) for n in fn.ro_names])
        hit = entry.bound.get(fixed)
        if hit is None:
            ro = self._exe._walk(scope, entry, program, fn.ro_names)
            rw = self._exe._walk(scope, entry, program, fn.rw_names, False)
            asked = [f or _open_format(v) for f, v in zip(fixed, ro)]
            lowered = fn.lower_bound(
                jax.tree_util.tree_map(_spec, self.example_feed),
                tuple(map(_spec, ro)), tuple(map(_spec, rw)),
                _spec(self._key0), asked)
            with coldstart.stage('compile', program):
                compiled = lowered.compile()
            hit = entry.bound[fixed] = (
                compiled, tuple(compiled.input_formats[0][1]))
            # the analytics mine THIS lowering: registered first, it is
            # what the run's own registration finds, which would lower
            # `flat`, that nobody has lowered, a second time (0.2 s a
            # program of 24 layers, in every process' set-up)
            analysis.record_compiled(
                types.SimpleNamespace(lower=lambda *avals: lowered),
                program, (self.example_feed,) + _by_name(fn, ro, rw)
                + (self._key0,), donate=bool(fn._donate))
        self._flat, self._formats = hit

    def _place(self, scope, names, leaves, program):
        """The entry's `place`: each read-only leaf in the executable's
        format (the read-written ones have none and stay; nor has a leaf
        the executable never reads -- a looped model's exit gate under a
        fetch list without its masses --, which stays as it lies)."""
        if names is self._entry.ro_names:
            todo = [i for i, f in enumerate(self._formats)
                    if f.layout is not None
                    and leaves[i].format.layout != f.layout]
            if todo:
                # set-up's `place` stage: the host waits for each copy
                with coldstart.stage('place', program), _compiled_here():
                    for i in todo:
                        self._relay(leaves, i)
        return tuple(leaves)

    def _relay(self, ro, i):
        """Leaf `i` of `ro` put into the entry's format, in `ro` and —
        where the scope holds that very array — in the scope. One leaf
        at a time, the old one let go before the next: a relaid weight
        beside itself is HBM nobody has."""
        name, fmt = self._entry.ro_names[i], self._formats[i]
        held = self._scope.get(name) is ro[i]
        ro[i] = jax.block_until_ready(jax.device_put(ro[i], fmt))
        if ro[i].format.layout != fmt.layout:
            raise RuntimeError('%r put into %s lies as %s'
                               % (name, fmt.layout, ro[i].format.layout))
        if held:
            self._scope._relay(name, ro[i])
        monitor.inc('executor_bound_relayout_total')
        self.relayouts += 1

    def __call__(self, feed, return_numpy=True):
        exe, entry, scope = self._exe, self._entry, self._scope
        rec = exe._take(scope, entry, self._program)
        if rec.ro is not self._ro:
            monitor.inc('executor_bound_restage_total')
            self.restages += 1
            self._ro = rec.ro
        key_arr = exe._next_key(self._program) if self._needs_rng \
            else self._key0
        fetches, _ = exe._commit(
            scope, entry, self._program, rec,
            *exe._call(entry, feed, rec, key_arr))
        return exe._fetch(entry, fetches, return_numpy)


class StepFuture(object):
    """Handle to one `Executor.run_async` step: device-resident fetches
    plus lazy host materialization.

    JAX dispatch is asynchronous, so the submitting call returns as soon
    as the step is staged; the device computes in the background while
    the host stages the next batch. ``result()`` blocks until the step
    completed and returns the fetch list (numpy by default;
    ``return_numpy=False`` keeps the fetches device-resident).
    ``wait()`` blocks without materializing. Any error — an injected
    run-site fault, a retry-exhausted dispatch, an async XLA runtime
    failure — surfaces HERE, on the future, never on the submitting
    ``run_async`` call.

    Futures complete in submission order (one device stream); waiting on
    a later future implies every earlier one finished.

    ``timing`` (after completion) is the step's structured latency
    breakdown: ``stage_s`` (host staging), ``execute_s`` (dispatch ->
    device completion, measured at the first wait), ``sync_s`` (host
    materialization in ``result(return_numpy=True)``), ``total_s``, and
    ``trace_id`` when the step carried a trace
    (docs/observability.md "Request & step tracing")."""

    __slots__ = ('_exe', '_outs', '_error', '_sync', '_done', '_trace',
                 '_tclaim', '_t0', '_wall0', '_stage_s', '_exec_s',
                 '_sync_s')

    def __init__(self, exe, outs, sync=None, error=None, trace=None,
                 stage_s=None):
        self._exe = exe
        self._outs = outs
        self._error = error
        self._sync = sync if sync is not None else outs
        self._done = error is not None
        self._trace = trace
        # single-element claim box: list.pop() is GIL-atomic, so exactly
        # ONE of two concurrent waiters (producer blocked in window
        # backpressure + consumer in result()) completes the trace —
        # both passing the unsynchronized _done check must not
        # double-count the execute stage or write the trace line twice
        self._tclaim = [trace] if trace is not None else []
        self._t0 = None if self._done else time.perf_counter()
        self._wall0 = time.time() * 1e6
        self._stage_s = stage_s
        self._exec_s = None
        self._sync_s = None

    def _ready_nonblock(self):
        if self._done:
            return True
        try:
            for leaf in jax.tree_util.tree_leaves(self._sync):
                ready = getattr(leaf, 'is_ready', None)
                if ready is not None and not ready():
                    return False
            return True
        except Exception:
            return False

    def done(self):
        """Non-blocking: has the step's device work completed (or
        failed)?"""
        return self._ready_nonblock()

    def wait(self):
        """Block until the step's device work completed; idempotent.
        Releases this future's slot in the executor's in-flight window.
        Returns self (so ``fut.wait().result()`` chains)."""
        if not self._done:
            if self._error is None:
                try:
                    jax.block_until_ready(self._sync)
                except Exception as e:  # noqa: BLE001 — surfaced in result
                    # async runtime failure: deliver on result(), exactly
                    # like a dispatch-time fault
                    self._error = e
            self._done = True
            if self._t0 is not None and self._exec_s is None:
                self._exec_s = time.perf_counter() - self._t0
            self._exe._inflight_discard(self)
            try:
                tr = self._tclaim.pop()
            except IndexError:
                tr = None
            if tr is not None:
                # the completion thread closes the step's trace: an
                # 'execute' stage spanning dispatch->device-complete plus
                # a span on THIS thread (which may not be the submitter —
                # the flow event links the hop in exported traces)
                if self._exec_s is not None:
                    tr.add_stage('execute', self._exec_s)
                    monitor.record_span('step.execute', self._wall0,
                                        self._exec_s * 1e6, trace=tr)
                tr.finish('error' if self._error is not None else 'ok',
                          error=self._error)
        return self

    def result(self, return_numpy=True):
        """The step's fetch list. Blocks until complete; raises the
        step's error if it failed. ``return_numpy=True`` materializes
        host-side, like ``run``; ``return_numpy=False`` returns the
        device arrays."""
        self.wait()
        if self._error is not None:
            raise self._error
        if not return_numpy:
            # mirror run(return_numpy=False): device arrays, except
            # lod-carrying results whose FetchedTensor wrap (deferred at
            # dispatch) is the point of asking for them
            return [_fetched(f.arr, f.lod) if isinstance(f, _DeferredFetch)
                    else f for f in self._outs]
        t_sync = time.perf_counter()
        out = [_fetched(f.arr, f.lod) if isinstance(f, _DeferredFetch)
               else np.asarray(f) for f in self._outs]
        if self._sync_s is None:
            self._sync_s = time.perf_counter() - t_sync
        return out

    def exception(self):
        """Block until complete; return the step's error (None on
        success) instead of raising it."""
        self.wait()
        return self._error

    @property
    def timing(self):
        """Structured latency breakdown of this step (None until the
        step completed): stage_s / execute_s / sync_s / total_s, plus
        trace_id when the step carried a trace."""
        if not self._done:
            return None
        parts = [s for s in (self._stage_s, self._exec_s, self._sync_s)
                 if s is not None]
        d = {'stage_s': self._stage_s, 'execute_s': self._exec_s,
             'sync_s': self._sync_s, 'total_s': sum(parts)}
        if self._trace is not None:
            d['trace_id'] = self._trace.trace_id
        return d


class _FeedSpec(object):
    """Shape/dtype stand-in for a staged run_fused batch — enough for
    _feed_signature (np.shape reads .shape, _dtype reads .dtype) without
    touching device data."""
    __slots__ = ('shape', 'dtype')

    def __init__(self, shape, dtype):
        if dtype is None:
            # the pre-stacked dict path documents arrays only; falling
            # through would put dtype('O') in the compile-cache key
            raise TypeError(
                "run_fused pre-stacked feeds must be arrays with a .dtype "
                "(np.ndarray or jax.Array); got a value of shape %r without "
                "one — np.stack plain lists before staging" % (shape,))
        self.shape = shape
        self.dtype = dtype


class Executor(object):
    def __init__(self, place=None):
        self.place = place if place is not None else TPUPlace(0)
        self._cache = _LRUCache()
        self._run_counter = 0
        # run_async bookkeeping: the sliding window of dispatched-but-not-
        # known-complete StepFutures (bounded by PADDLE_MAX_INFLIGHT_STEPS)
        self._inflight = collections.deque()
        self._async_cv = threading.Condition(threading.Lock())
        self._pending_submit = 0        # reserved-but-not-yet-appended
        self._inflight_peak = 0

    def close(self):
        # flush any in-flight async steps first — their device work may
        # still reference compiled entries
        self.drain_async()
        # drops this executor's view only; the process-wide fingerprint
        # cache keeps entries alive for other executors (it is LRU-bounded,
        # so close() is no longer load-bearing for memory)
        self._cache.clear()

    @staticmethod
    def _py_reader_feed(program, feed):
        """Started py_readers supply their variables when not explicitly
        fed (reference create_py_reader_op pulling the blocking queue) —
        shared by run() and run_async() so the two paths cannot
        diverge."""
        src_prog = getattr(program, '_program', program)  # CompiledProgram
        for rd in getattr(src_prog, '_py_readers', []):
            if rd._thread is not None and not any(
                    v.name in (feed or {}) for v in rd._vars):
                feed = dict(feed or {})
                feed.update(rd._next_feed())
        return feed

    # ------------------------------------------------------------------
    # async pipeline bookkeeping
    @staticmethod
    def _max_inflight():
        """Window size for run_async: how many dispatched steps may be
        pending at once. 2 (the double-buffer classic) overlaps step
        N+1's host staging with step N's device compute while bounding
        extra HBM to one step's working set."""
        try:
            return max(1, int(os.environ.get('PADDLE_MAX_INFLIGHT_STEPS',
                                             '') or 2))
        except ValueError:
            return 2

    def _inflight_discard(self, fut):
        with self._async_cv:
            try:
                self._inflight.remove(fut)
            except ValueError:
                return
            # gauge published under the lock: a descheduled writer must
            # not overwrite a newer depth with its stale value
            monitor.set_gauge('executor_inflight',
                              float(len(self._inflight)))
            self._async_cv.notify_all()

    def drain_async(self):
        """Wait for every in-flight `run_async` step (oldest first);
        returns how many were waited on. Errors stay on their futures —
        draining never raises."""
        n = 0
        while True:
            with self._async_cv:
                if not self._inflight:
                    return n
                fut = self._inflight[0]
            fut.wait()
            n += 1

    # ------------------------------------------------------------------
    def _cache_get(self, key):
        entry = self._cache.get(key)
        if entry is None:
            entry = _shared_cache.get(key)
            if entry is not None:
                self._cache[key] = entry
        return entry

    def _cache_put(self, key, entry):
        self._cache[key] = entry
        _shared_cache[key] = entry

    # ------------------------------------------------------------------
    def _feed_signature(self, feed, feed_lods=(), static_feed=()):
        feed_lods = dict(feed_lods) if feed_lods else {}
        static_feed = dict(static_feed) if static_feed else {}

        def _dtype(v):
            # metadata only — np.asarray on a device jax.Array fetches the
            # WHOLE buffer host-side (measured 1.5 s/call on run_fused's
            # stacked feeds; this key is computed every run)
            dt = getattr(v, 'dtype', None)
            return str(dt) if dt is not None else str(np.asarray(v).dtype)

        sig = tuple(sorted((k, tuple(np.shape(v)), _dtype(v))
                           for k, v in feed.items()))
        lod_sig = tuple(sorted(feed_lods.items()))
        static_sig = tuple(sorted(
            (k, v.tobytes()) for k, v in static_feed.items()))
        # the fused-kernel tier changes how fusable ops LOWER, so it keys
        # the compiled entry (flipping PADDLE_FUSED_TIER recompiles instead
        # of serving stale kernels). cache_token() is one env-dict read —
        # the whole per-run cost of the tier on the hot path; resolution
        # and the dispatch counters happen at trace time only.
        from .ops.kernel_tier import cache_token
        return sig, lod_sig, static_sig, cache_token()

    @staticmethod
    def _split_lod_feed(value):
        """A feed value may be array-like, (array, lod) like the reference's
        OpTest/DataFeeder convention, or a LoDTensor from create_lod_tensor."""
        if isinstance(value, tuple) and len(value) == 2 and \
                isinstance(value[1], (list, tuple)):
            return value[0], normalize_lod(value[1])
        lod_m = getattr(value, 'lod', None)
        if callable(lod_m) and not isinstance(value, np.ndarray):
            return np.asarray(value), normalize_lod(lod_m())
        if isinstance(value, FetchedTensor):
            return np.asarray(value), normalize_lod(value.lod())
        return value, ()

    def _prepare_feed(self, program, feed, count=True):
        out, lods = {}, {}
        host_bytes = 0
        gb = program.global_block()
        for name, value in feed.items():
            value, lod = self._split_lod_feed(value)
            var = gb._find_var_recursive(name)
            # already-device feeds (a staged input pipeline, a
            # return_numpy=False fetch fed back in) pass through untouched:
            # np.asarray would pull the whole buffer host-side only for the
            # run to re-upload it
            arr = value if isinstance(value, jax.Array) else np.asarray(value)
            if var is not None and var.dtype is not None and \
                    arr.dtype != var.dtype:
                tgt = np.dtype(var.dtype)
                if isinstance(arr, jax.Array):
                    # device-resident feed (a prefetcher-staged batch):
                    # x64-disabled jax already narrowed 64-bit dtypes at
                    # device_put, so coerce toward what the device can
                    # actually hold — an astype back to int64 would be a
                    # no-op that warns on every run
                    from jax import dtypes as _jax_dtypes
                    tgt = np.dtype(_jax_dtypes.canonicalize_dtype(tgt))
                # feeding python lists of ints to a float var etc.
                if arr.dtype == tgt:
                    pass
                elif arr.dtype.kind in 'iub' and tgt.kind in 'iub':
                    arr = arr.astype(tgt)
                elif arr.dtype.kind == 'f' and tgt.kind == 'f':
                    arr = arr.astype(tgt)
                elif arr.dtype == np.float64:
                    arr = arr.astype(tgt)
            out[name] = arr
            if not isinstance(arr, jax.Array):
                # host-staged feed bytes (device jax.Array feeds pass
                # through without a host->device transfer and don't count)
                host_bytes += int(getattr(arr, 'nbytes', 0))
            if lod:
                if lod[-1][-1] != arr.shape[0]:
                    raise ValueError(
                        "feed %r: LoD %s does not cover the array's leading "
                        "dim %d — offsets' last entry must equal it (pass "
                        "lengths via create_lod_tensor / "
                        "recursive_sequence_lengths)"
                        % (name, [list(l) for l in lod], arr.shape[0]))
                lods[name] = lod
        if host_bytes and count:
            # count=False: metadata-only callers (Executor.explain, the
            # NaN-provenance replay) stage nothing host->device
            monitor.inc('feed_host_bytes', host_bytes)
        return out, lods

    def _prepare_run_inputs(self, program, feed, scope, fetch_list,
                            count=True):
        """Shared feed/fetch/static preparation for every run-shaped
        entry point (_run_impl, Executor.explain, the profiled and
        NaN-provenance replays in analysis.py). The compile-cache key is
        built from these values, so they MUST be produced identically
        everywhere — explain seeding the run cache depends on it.
        Returns (feed, fetch_names, static_feed, static_lods)."""
        feed, feed_lods = self._prepare_feed(program, feed or {},
                                             count=count)
        fetch_names = [v.name if isinstance(v, Variable) else v
                       for v in (fetch_list or [])]
        static_names = self._static_feed_names(program)
        static_feed = {n: np.asarray(feed[n]) for n in static_names
                       if n in feed}
        return (feed, fetch_names, static_feed,
                self._static_lods(scope, feed_lods))

    @staticmethod
    def _static_lods(scope, feed_lods):
        """Scope-held LoD state binds statically, like a feed's LoD — and
        is part of the cache key, or a compile baked with a stale scope
        LoD would be reused after the scope's LoD changes."""
        static_lods = {n: normalize_lod(l)
                       for n, l in getattr(scope, '_lods', {}).items() if l}
        static_lods.update(feed_lods)
        return static_lods

    @staticmethod
    def _static_feed_names(program):
        """Feed names consumed through a `static_inputs` slot of any op —
        their values are compile-time constants (shape-bearing)."""
        cached = getattr(program, '_static_names_cache', None)
        if cached is not None and cached[0] == program._version:
            return cached[1]
        names = set()
        for block in program.blocks:
            for op in block.ops:
                if not has_op(op.type):
                    continue
                for slot in get_op(op.type).static_inputs:
                    names.update(op.input(slot))
        program._static_names_cache = (program._version, names)
        return names

    def run(self, program=None, feed=None, fetch_list=None, feed_var_name='feed',
            fetch_var_name='fetch', scope=None, return_numpy=True,
            use_program_cache=True, donate=None):
        """donate: per-call override of the buffer-donation default for
        THIS run only (None = resolve from env/backend as usual). False is
        the rollback/serving contract — the pre-run state buffers stay
        alive after the call — without flipping the process-global
        PADDLE_DONATE env var under other threads' runs."""
        if program is None:
            program = default_main_program()
        feed = self._py_reader_feed(program, feed)
        # CompiledProgram support is injected by compiler.py via duck-typing:
        if hasattr(program, '_executor_run'):
            return program._executor_run(self, feed, fetch_list, scope,
                                         return_numpy, donate=donate)
        return self._run_plain(program, feed, fetch_list, scope,
                               return_numpy, use_program_cache, donate)

    def _run_plain(self, program, feed, fetch_list, scope, return_numpy,
                   use_program_cache, donate):
        """run() of a plain Program, instrumented: 'run' span + per-run
        wall-latency histogram (the delegating paths of run() recurse into
        it and would double-count). The counter counts ATTEMPTS — a run
        that raises (nan check, bad feed) must not vanish from the rate.
        step_scope: a bare run with no ambient trace may start its own
        head-sampled 'step' trace (PADDLE_TRACE_SAMPLE); the sampled-out
        path costs one env read + one thread-local read + one random()."""
        with trace_mod.step_scope('step'):
            with monitor.timed_span('run', 'executor_run_seconds'):
                monitor.inc('executor_run_total')
                if analysis.profile_ops_active():
                    # op-attribution mode (PADDLE_PROFILE_OPS /
                    # profile_ops()): interpret the program op by op
                    return analysis.run_profiled(self, program, feed,
                                                 fetch_list, scope,
                                                 return_numpy)
                entry, fetches, _ = self._run_impl(
                    program, feed, fetch_list, scope, use_program_cache,
                    donate)
                return self._fetch(entry, fetches, return_numpy)

    # ------------------------------------------------------------------
    def run_async(self, program=None, feed=None, fetch_list=None,
                  scope=None, donate=None, use_program_cache=True):
        """Dispatch one step WITHOUT waiting for its results: returns a
        `StepFuture` (device-resident fetches + lazy host
        materialization) as soon as the step is staged, so the host can
        assemble batch N+1 — or a `DevicePrefetcher` can device_put it —
        while the device computes step N.

        The pipeline depth is bounded: at most ``PADDLE_MAX_INFLIGHT_STEPS``
        (default 2) dispatched steps may be pending per executor. A
        submission against a full window first waits for the OLDEST
        in-flight step (counted in ``executor_pipeline_stall_total``,
        timed in ``step_wait_seconds``), so device memory holds at most
        window+1 steps' feeds/results — async dispatch never turns into
        unbounded HBM growth. ``executor_inflight`` /
        ``executor_inflight_peak`` gauges expose the live depth;
        ``stage_seconds`` times the host-side staging of each submission.

        Donation interacts with overlap: a donated rw buffer from step N
        could still back step N-1's un-materialized fetches, so when the
        resolved donation policy would be ON this path forces it OFF and
        counts ``donation_fallback_total{reason=inflight}`` — run_async
        trades one extra state copy in HBM for overlap. The computed
        TRAJECTORY is identical to `run`'s (same RNG stream, same
        compiled math): tests pin bit-equality.

        Failures — injected run-site faults, retry-exhausted dispatches,
        async XLA errors — surface on ``StepFuture.result()``, never on
        this call. FLAGS_check_nan_inf still checks at the program
        boundary, which materializes state host-side and forfeits most
        overlap (debugging flag — documented tradeoff)."""
        if program is None:
            program = default_main_program()
        feed = self._py_reader_feed(program, feed)
        window = self._max_inflight()
        while True:
            with self._async_cv:
                # the reservation (not the append) claims the slot, so
                # concurrent submitters on one executor can never exceed
                # the window between check and append
                if len(self._inflight) + self._pending_submit < window:
                    self._pending_submit += 1
                    break
                oldest = self._inflight[0] if self._inflight else None
            if oldest is None:
                # window held entirely by other threads' reservations:
                # wait for their dispatches to land
                with self._async_cv:
                    self._async_cv.wait(0.05)
                continue
            if oldest._ready_nonblock():
                oldest.wait()       # already complete: free the slot
                continue
            # genuine stall: the window is full of still-running steps
            monitor.inc('executor_pipeline_stall_total')
            t0 = time.perf_counter()
            oldest.wait()
            monitor.observe('step_wait_seconds',
                            time.perf_counter() - t0)
        # a bare async step with no ambient trace may start its own
        # head-sampled trace; it travels on the future and is finished by
        # whichever thread completes the step (wait/result)
        own = trace_mod.maybe_trace('step')
        t0 = time.perf_counter()
        monitor.inc('executor_run_async_total')
        donate_override = donate
        if _donation_enabled(override=donate, record=False):
            donate_override = 'inflight'
        sync = None
        try:
            with trace_mod.activate(own):
                with monitor.span('run_async'):
                    if hasattr(program, '_executor_run'):
                        # CompiledProgram delegation has its own dispatch
                        # path; run it synchronously and hand back a
                        # completed future (correct, without overlap)
                        outs = program._executor_run(
                            self, feed, fetch_list, scope, False,
                            donate=False if donate_override == 'inflight'
                            else donate)
                    elif analysis.profile_ops_active():
                        outs = analysis.run_profiled(self, program, feed,
                                                     fetch_list, scope,
                                                     False)
                    else:
                        # the fetches stay on the device: a LoD-carrying
                        # one is wrapped when the future materializes
                        # (np.asarray here would block the submission on
                        # the device step). What the future waits on: the
                        # fetches and commit's token, one state leaf —
                        # a fetch-less step still gives StepFuture.wait
                        # something device-side to block on (the single
                        # device stream orders everything else behind it)
                        entry, fetches, token = self._run_impl(
                            program, feed, fetch_list, scope,
                            use_program_cache, donate_override)
                        sync = (fetches, token)
                        outs = [_DeferredFetch(f, entry.lod_out[n])
                                if entry.lod_out.get(n) else f
                                for n, f in zip(entry.fetch_names, fetches)]
        except Exception as e:      # noqa: BLE001 — delivered on the future
            with self._async_cv:
                self._pending_submit -= 1
                self._async_cv.notify_all()
            stage_s = time.perf_counter() - t0
            monitor.observe('stage_seconds', stage_s)
            if own is not None:
                # a staging failure never reaches wait(): close the
                # trace here so the error is kept (keep-errors); the
                # future still carries it so fut.timing names the
                # trace_id (wait() never re-finishes a _done future)
                own.add_stage('stage', stage_s)
                own.finish('error', error=e)
            return StepFuture(self, None, error=e, trace=own,
                              stage_s=stage_s)
        stage_s = time.perf_counter() - t0
        if own is not None:
            own.add_stage('stage', stage_s)
        fut = StepFuture(self, outs, sync=sync, trace=own, stage_s=stage_s)
        with self._async_cv:
            self._pending_submit -= 1
            self._inflight.append(fut)
            n = len(self._inflight)
            if n > self._inflight_peak:
                self._inflight_peak = n
            # gauges published under the lock (stale-writer-last would
            # understate the peak the window tests assert on)
            monitor.set_gauge('executor_inflight', float(n))
            monitor.set_gauge('executor_inflight_peak',
                              float(self._inflight_peak))
            self._async_cv.notify_all()
        monitor.observe('stage_seconds', stage_s)
        return fut

    @staticmethod
    def _host_splittable(program):
        """PADDLE_SEGMENT_HOST_OPS=1: whether the program's host ops can
        be split out into segments of their own. Memoized per program
        version: the common (host-op-free) training step must not rescan
        the op list every call."""
        cached = getattr(program, '_host_split_cache', None)
        if cached is None or cached[0] != program._version:
            main_ops = program.global_block().ops
            host_pos = [i for i, op in enumerate(main_ops)
                        if op.type in _HOST_SEGMENT_OPS]
            bwd_pos = [i for i, op in enumerate(main_ops)
                       if op.type == 'backward']
            # a host op inside a differentiated forward span cannot
            # be split out (it would cut the jax.vjp closure) — those
            # keep the callback path (py_func backward_func is itself
            # a callback)
            splittable = bool(host_pos) and (
                not bwd_pos or min(host_pos) > max(bwd_pos))
            cached = (program._version, splittable)
            program._host_split_cache = cached
        return cached[1]

    def _entry_key(self, program, feed, static_lods, static_feed,
                   fetch_names, donate=None, kind=(), record=True):
        """THE compile-cache key of (program, prepared feed, static LoDs
        and feeds, fetch names, donation) for an entry of `kind` — () a
        run's, and so bind's, precompile's, explain's and the warm
        farm's; ('hostseg',) a segmented run's plan; ('fused', staged,
        steps) a fused window; ('mesh', ...) a runner's, in the runner's
        own cache. `donate` is the caller's override: the policy
        resolves here (`_donation_enabled`; record=False for a QUERY —
        an AOT pass, a report, a runner whose donation is its jit's —
        that must not move the donation rates), behind the provenance
        replay's force-off: PADDLE_NAN_LOCALIZE re-runs a step that trips
        FLAGS_check_nan_inf against its PRE-run state, so its buffers
        must survive the call."""
        if donate is None and analysis.nan_localization_enabled():
            from . import flags as _flags
            if _flags.get_flags('check_nan_inf'):
                donate = False
        return kind + (program._fingerprint(),
                       self._feed_signature(feed, static_lods, static_feed),
                       tuple(fetch_names),
                       _donation_enabled(override=donate, record=record))

    def _find(self, key, program, build, cache=None,
              missed='compile_cache_miss', book=_RUN_COMPILE):
        """The entry under `key` — in this executor's cache and the
        process-wide one behind it, or in `cache` (a runner's own) —
        and None; or, made by `build()` now (set-up's `trace` stage,
        the 'compile' site's faults and retries), the new entry and
        when its making began: its first call is the compile."""
        entry = self._cache_get(key) if cache is None else cache.get(key)
        if entry is not None:
            monitor.inc('compile_cache_hit')
            return entry, None
        monitor.inc(missed)
        since = time.perf_counter()
        # wired at first compile, not Executor construction: building an
        # executor must stay free of backend initialization (io-only
        # executors, launcher parents that must not claim the chip)
        _wire_persistent_cache()

        def attempt():
            resilience.maybe_fault('compile')
            return build()
        with coldstart.stage('trace', program, *book):
            try:
                entry = attempt()
            except Exception as e:      # noqa: BLE001 — classified inside
                entry = resilience.retry_after(e, attempt, site='compile')
        if cache is None:
            self._cache_put(key, entry)
        else:
            cache[key] = entry
        return entry, since

    def _build_entry(self, program, feed, fetch_names, static_lods,
                     static_feed, donate, **how):
        """Lower the program for this signature (the jitted function
        compiles inside its first call). `how`: the `lower_params` of a
        segment, what its `_CompiledEntry` is told."""
        read, written = lowering.analyze_state(program, fetch_names)
        # only require state read before being written this run
        needed = self._read_before_write(program, read, written,
                                         set(feed), fetch_names)
        lod_out = {}
        fn, _, _ = lowering.build_callable(
            program, fetch_names, needed, written,
            static_lods=static_lods, static_feed=static_feed,
            lod_out=lod_out, donate=donate,
            lower_params=how.pop('lower_params', None))
        return _CompiledEntry(fn, fetch_names, written, program, lod_out,
                              **how)

    def _run_impl(self, program, feed, fetch_list, scope, use_program_cache,
                  donate_override=None):
        """One run up to its fetches, still on the device: (the entry —
        its fetch names and LoDs, for `_fetch` —, the fetches, commit's
        completion token)."""
        if scope is None:
            scope = global_scope()
        if os.environ.get('PADDLE_SEGMENT_HOST_OPS') == '1' \
                and self._host_splittable(program):
            with _run_phase('prepare'):
                feed, fetch_names, static_feed, static_lods = \
                    self._prepare_run_inputs(program, feed, scope,
                                             fetch_list)
                with _run_phase('segmented'):
                    return self._run_segmented(
                        program, feed, fetch_names, scope, static_lods,
                        static_feed, donate_override)
        cache, missed = (None, 'compile_cache_miss') if use_program_cache \
            else ({}, 'compile_cache_bypass')
        fetch_names = static_feed = static_lods = None

        def find():
            nonlocal fetch_names, static_feed, static_lods
            feed2, fetch_names, static_feed, static_lods = \
                self._prepare_run_inputs(program, feed, scope, fetch_list)
            key = self._entry_key(program, feed2, static_lods, static_feed,
                                  fetch_names, donate_override)
            return self._find(
                key, program, lambda: self._build_entry(
                    program, feed2, fetch_names, static_lods, static_feed,
                    key[-1]), cache, missed) + (feed2,)

        def collect(entry, feed2, rec, key_arr, fetches):
            # TPU second-place validation (reference op_test.py:304
            # check_output_with_place / the mkldnn-suite reuse pattern):
            # record executed (program, feed, state, key, CPU fetches)
            # cases for tools/tpu_optest.py to replay on the real chip
            from .core.optest_collect import record_case
            record_case(program, feed2, static_lods,
                        *_by_name(entry.fn, rec.ro, rec.rw), key_arr,
                        fetch_names, fetches)

        def localize(feed2, key_arr, e, ro_state, rw_state):
            # PADDLE_NAN_LOCALIZE=1: replay the step op-by-op against the
            # still-alive pre-run state and name the first op that
            # produced a non-finite value (no-op when disabled)
            info = analysis.localize_nonfinite(
                program, feed2, ro_state, rw_state, key_arr, static_lods,
                static_feed)
            if info is None:
                return e
            err = RuntimeError('%s; %s' % (
                e, analysis.format_localization(info)))
            # carried for TrainingGuard: the guard must reuse this
            # localization, not pay a second replay (and double-count
            # nonfinite_localized_total)
            err.nonfinite_localization = info
            return err
        return self._step(
            scope, program, find, localize,
            collect if os.environ.get('PADDLE_OPTEST_COLLECT_DIR') else None)

    # ------------------------------------------------------------------
    # the step: one compiled call on a scope
    def _step(self, scope, program, find, localize=None, collect=None,
              phase=_run_phase, book=_RUN_COMPILE):
        """One compiled call in Executor.run's phases (_run_phase) —
        `run`'s, `bind`'s first and the SPMD runners': prepare — `find()`
        (the caller's feed preparation and checks, the key, the entry:
        `_find`'s pair and the prepared feed), the state, the run key,
        the flight recorder's step note; dispatch — the call (or compile,
        on a signature's first: set-up's frame); commit. Returns (entry,
        fetches on the device, commit's completion token): `_fetch` is
        the fetch phase. `phase`, `book`: none for a fused window, which
        is no run."""
        with phase('prepare'):
            entry, since, feed = find()
            rec = self._take(scope, entry, program)
            key_arr = self._next_key(program)
            blackbox.note_step(program)
        if since is not None:
            frame = coldstart.compile_frame(program, 'first_run', since,
                                            *book)
            fetches, new_state = _first_call(frame, entry, feed, rec,
                                             key_arr)
            self._compiled(entry, program, feed, rec, key_arr, frame)
            # goodput accounting: fresh compiles land in the 'compile'
            # loss bucket instead, keeping execute baselines clean
            times = None
        else:
            with phase('dispatch'):
                fetches, new_state, times = self._call(entry, feed, rec,
                                                       key_arr)
        with phase('commit'):
            if collect is not None:
                collect(entry, feed, rec, key_arr, fetches)
            return (entry,) + self._commit(
                scope, entry, program, rec, fetches, new_state, times,
                localize and functools.partial(localize, feed, key_arr))

    @staticmethod
    def _compiled(entry, program, feed, rec, key_arr, frame):
        """A new entry's first call is behind it: the compile counts for
        the recompile-storm sentinel, and the executable registers for
        XLA cost/memory analytics (lazy: mined when snapshot / explain /
        costreport first looks) under the kind its dispatches are booked
        as — none for a segment's clone."""
        goodput.note_compile(entry.fp, frame.seconds)
        kind = goodput._ANALYSIS_KIND.get(entry.kind)
        if kind is not None:
            analysis.record_compiled(
                entry.fn, program,
                (feed,) + _by_name(entry.fn, rec.ro, rec.rw) + (key_arr,),
                kind=kind, donate=bool(entry.fn._donate), steps=entry.steps)

    def _walk(self, scope, entry, program, names, cache=True):
        """`names` of `entry`'s state looked up in the scope
        (`_state_value`: uploaded where the host wrote it; cache=False
        for the read-written ones, which the run rebinds) and put where
        the entry wants them: a tuple in `names`' order."""
        return entry.place(scope, names,
                           [self._state_value(scope, n, program, cache=cache)
                            for n in names], program)

    def _take(self, scope, entry, program):
        """*take*: the entry's state from the scope, as a `_Held` — the
        record the entry's last call left (`_commit`) as far as it still
        holds, else the walk. The record is TAKEN: a call that raises
        leaves none, and the donated leaves are the step's alone to let
        go. `executor_run_carried_total` counts the takes that looked
        nothing up."""
        rec = scope._held.pop(entry, None)
        if rec is scope._fresh:
            scope._fresh = None
        if rec is None or rec.gen != scope._gen:
            names = entry.ro_names
            staged = scope._staged
            for n in names:
                staged.setdefault(n)
            ro = self._walk(scope, entry, program, names)
            rw = self._walk(scope, entry, program, entry.rw_names, False)
            # read AFTER the walk (an upload cached back into the scope
            # is a write too) and BEFORE the comparison: a write that
            # lands later moves the count, one that landed earlier fails
            # the comparison. What the scope does not hold is a host
            # value converted for this call alone: nothing is kept
            gen = scope._gen
            held = all(map(operator.is_, ro, map(scope.get, names)))
            rec = _Held(ro, gen if held else None, rw)
        elif rec.rw is None or rec.writes != scope._writes:
            rec.rw = self._walk(scope, entry, program, entry.rw_names, False)
        else:
            monitor.inc('executor_run_carried_total')
        return rec

    def _next_key(self, program):
        """The run key (`_run_key`), the counters it is made of moved."""
        self._run_counter += 1
        key_arr = _run_key(program.random_seed, _next_program_run(program),
                           self._run_counter)
        # the step's PRNG key, kept for debug replays (TrainingGuard's
        # NaN-provenance pass must reproduce the failed step's
        # randomness)
        program._last_run_key = key_arr
        return key_arr

    @staticmethod
    def _call(entry, feed, rec, key_arr):
        """*call*: the entry's compiled function on the taken state —
        (fetches, new state, when the dispatch began and ended). THE
        'run' fault site. The success path pays one fault-site check and
        a try frame; retry machinery engages only after an exception
        actually escaped (and never with consumed donated buffers —
        resilience._buffers_alive guards the re-invoke)."""
        ro, rw = rec.ro, rec.rw

        def attempt():
            resilience.maybe_fault('run')
            return entry.call(feed, ro, rw, key_arr)
        t_disp = time.perf_counter()
        try:
            fetches, new_state = attempt()
        except Exception as e:          # noqa: BLE001 — classified inside
            fetches, new_state = entry.retry(
                e, attempt, site='run', state=dict(zip(entry.rw_names, rw)))
            # failed attempts + backoff sleeps are the retry_backoff
            # loss bucket, not device-busy: restart the window at the
            # successful dispatch so the completer's serial attribution
            # only covers real execute
            t_disp = time.perf_counter()
        return fetches, new_state, (t_disp, time.perf_counter())

    def _commit(self, scope, entry, program, rec, fetches, new_state,
                times=None, localize=None):
        """*commit*: what `_call` returned lands. The dispatch is booked
        to goodput under the entry's kind (`times`: None for a first
        call, a compile, and for a host segment); the scope is rebound;
        FLAGS_check_nan_inf checks, `localize(e, ro_state, rw_state)`
        giving the error to raise where the caller asked; FLAGS_benchmark
        waits; the record for the entry's next take is left; LoDs and
        checkpoint_notify follow. Returns (the fetches, sparse ones made
        dense; one state leaf as a completion token, or None)."""
        if times is not None:
            goodput.note_dispatch(entry.fp, entry.kind, times[0], times[1],
                                  leaf=_goodput_leaf(new_state, fetches),
                                  steps=entry.steps)
        # rebind the scope BEFORE the nan-check can raise: with
        # donation on, the pre-run rw buffers are already consumed, so
        # bailing out here would leave the scope pointing at deleted
        # arrays — a NaN state is at least readable/checkpointable for
        # debugging
        gen = scope._gen
        scope.update(new_state)
        from . import flags as _flags
        if _flags.get_flags('check_nan_inf'):
            try:
                _check_nan_inf(new_state,
                               dict(zip(entry.fetch_names, fetches)),
                               entry.to_host)
            except RuntimeError as e:
                if localize is None:
                    raise
                raise localize(
                    e, *_by_name(entry.fn, rec.ro, rec.rw)) from None
        if _flags.get_flags('benchmark'):
            # block on the new state too: timing only fetches
            # under-measures steps whose outputs are all state writes
            # (pure-train steps fetching just a scalar loss, or nothing
            # at all). The synced wait lands in executor_sync_seconds —
            # the device-completion tail FLAGS_benchmark exists to expose
            t_sync = time.perf_counter()
            with _run_phase('fetch'):
                jax.block_until_ready((fetches, new_state))
            monitor.observe('executor_sync_seconds',
                            time.perf_counter() - t_sync)
        # the record for the next take: the read-only leaves this call
        # was given — the entry's own rebind, which writes none of them,
        # is the one write they survive — and the read-written ones it
        # returned. The donated inputs are let go HERE, behind the
        # dispatch and while the device is busy, not as the frames exit
        # behind the fetch's wait: a thousand arrays take their time
        rec.rw = None
        if rec.gen is not None and rec.gen == gen:
            rec.gen, rec.writes = scope._gen, scope._writes
            rec.rw = tuple(map(new_state.__getitem__, entry.rw_names))
            entry.keep(scope, entry, rec)
        # checkpoint_notify (ops/dist_ops.py): the reference RPCs the
        # checkpoint dir to pservers each execution; here the executor
        # is the checkpoint writer, so save persistables after the run
        for cn_dir in entry.notify_dirs:
            from .io import save_persistables
            with scope_guard(scope):
                save_persistables(self, cn_dir, main_program=program)
        # propagate LoD of written persistables into the scope (nothing
        # to do where the entry gives no LoD and the scope holds none)
        if entry.lod_out or scope._lods:
            for n in entry.written:
                lod = entry.lod_out.get(n)
                if lod:
                    scope._lods[n] = lod
                else:
                    scope._lods.pop(n, None)
        from .core.selected_rows import SelectedRows
        # fetched sparse grads densify, like the reference's fetch of
        # a SelectedRows var materializing a tensor
        return ([f.to_dense() if isinstance(f, SelectedRows) else f
                 for f in fetches],
                next(iter(new_state.values()), None))

    @staticmethod
    def _fetch(entry, fetches, return_numpy):
        """*fetch*: the fetches as the caller asked for them — on the
        host (the `fetch` phase: the wait for the device), or left on the
        device (no host sync); a LoD-carrying one is wrapped either way,
        since the LoD metadata is the point of asking for it."""
        host, lods = entry.to_host, entry.lod_out
        with _run_phase('fetch') if return_numpy \
                else contextlib.nullcontext():
            return [_fetched(host(f), lods[n]) if lods.get(n)
                    else host(f) if return_numpy else f
                    for n, f in zip(entry.fetch_names, fetches)]

    # ------------------------------------------------------------------
    def _segment_plan(self, program, fetch_names):
        """Split the main block at host-callback ops into parts
        [('dev', lo, hi) | ('host', i, i+1)]; for each part precompute its
        sub-program (a clone with the op slice), the values it consumes
        from earlier parts/feeds, and the crossing vars it must fetch."""
        ops = program.global_block().ops
        parts = []
        lo = 0
        for i, op in enumerate(ops):
            if op.type in _HOST_SEGMENT_OPS:
                if i > lo:
                    parts.append(('dev', lo, i))
                parts.append(('host', i, i + 1))
                lo = i + 1
        if lo < len(ops):
            parts.append(('dev', lo, len(ops)))

        def _rw_sets(part_ops):
            """(reads, writes) of the ops incl. nested control-flow blocks
            (whose bodies touch parent vars not listed on the parent op);
            reads exclude names the part itself produced first."""
            reads, writes = set(), set()

            from .framework import SUB_BLOCK_ATTRS

            def _walk(op_list):
                for op in op_list:
                    reads.update(n for n in op.input_arg_names
                                 if n not in writes)
                    for a in SUB_BLOCK_ATTRS:
                        idx = getattr(op, 'attrs', {}).get(a)
                        if idx is not None:
                            _walk(program.block(int(idx)).ops)
                    writes.update(op.output_arg_names)
            _walk(part_ops)
            return reads, writes

        part_rw = [_rw_sets(ops[plo:phi]) for _, plo, phi in parts]
        plan = []
        for k, (kind, plo, phi) in enumerate(parts):
            sub = program.clone()
            sub.global_block().ops = sub.global_block().ops[plo:phi]
            ins = part_rw[k][0]
            later_ins = set()
            later_written = set()
            for reads_q, writes_q in part_rw[k + 1:]:
                later_ins |= reads_q
                later_written |= writes_q
            produced = set()
            for op in ops[plo:phi]:
                produced.update(op.output_arg_names)
            gb = program.global_block()
            crossing = sorted(
                n for n in produced
                if (n in later_ins or n in fetch_names)
                and not (gb._find_var_recursive(n) is not None
                         and gb._find_var_recursive(n).persistable))
            plan.append({'kind': kind, 'sub': sub, 'ins': ins,
                         'crossing': crossing, 'lo': plo,
                         'later_written': later_written})
        return plan

    def _run_segmented(self, program, feed, fetch_names, scope, static_lods,
                       static_feed, donate_override=None):
        """Heterogeneous execution for backends without host callbacks: see
        _HOST_SEGMENT_OPS. Device segments are compiled and cached like
        normal runs; host ops run eagerly on the CPU backend with only the
        crossing vars transferred. A part is the step's `_call` (a device
        part) and `_commit`, not its `_take`: the parts of one run share
        its ONE run key, and a part's state is walked by the plan's rule
        (below) — so no record is kept either (`_Held.gen` None)."""
        monitor.inc('executor_run_segmented_total')
        key = self._entry_key(program, feed, static_lods, static_feed,
                              fetch_names, donate_override, ('hostseg',))
        plan = self._cache_get(key)
        if plan is None:
            monitor.inc('compile_cache_miss')
            plan = self._segment_plan(program, fetch_names)
            self._cache_put(key, plan)
        else:
            monitor.inc('compile_cache_hit')

        # kept for debug replays, as in a plain run (TrainingGuard's NaN
        # provenance must not fall back to PRNGKey(0) for host-op programs)
        key_arr = self._next_key(program)
        blackbox.note_step(program)
        val_env = dict(feed)
        lod_env = dict(static_lods)
        for seg in plan:
            sub, host = seg['sub'], seg['kind'] == 'host'
            seg_feed = {n: v for n, v in val_env.items() if n in seg['ins']}
            entry = seg.get('entry')
            fresh = entry is None
            if fresh:
                _wire_persistent_cache()

                def _build_segment():
                    resilience.maybe_fault('compile')
                    # op_offset = the segment's slice start in the
                    # original block, so every op derives the SAME per-op
                    # PRNG key as the unsegmented program (rng streams
                    # must not depend on where host ops split the
                    # program, and two RNG ops at equal within-segment
                    # indices must not collide)
                    return self._build_entry(
                        sub, seg_feed, list(seg['crossing']), lod_env,
                        static_feed, key[-1] and not host, kind='segmented',
                        fp=key[1],      # booked to the whole program
                        lower_params=dict({'op_offset': seg['lo']},
                                          **({'host_eager': True}
                                             if host else {})))
                # segment build cost (the jit compile itself is lazy and
                # lands in this segment's first call below, a set-up
                # frame of its own; device-segment granularity is close
                # enough for the rare hostseg path)
                with _compile_frame(sub, 'trace') as frame:
                    try:
                        entry = _build_segment()
                    except Exception as e:  # noqa: BLE001 — classified inside
                        entry = resilience.retry_after(e, _build_segment,
                                                       site='compile')
                # the program's checkpoint_notify saves follow the LAST
                # part's commit
                entry.notify_dirs = _notify_dirs(program) \
                    if seg is plan[-1] else []
                seg['entry'] = entry
                goodput.note_compile(key[1], frame.seconds)
            # cache=False also for names a LATER segment writes: caching
            # would freeze the caller's init buffer writeable=False even
            # though the scope is rebound right after that later segment —
            # the rw-path exemption applies program-wide, not per-segment
            later_w = seg.get('later_written', ())
            rec = _Held(
                tuple([self._state_value(scope, n, program,
                                         cache=n not in later_w)
                       for n in entry.ro_names]), None,
                tuple([self._state_value(scope, n, program, cache=False)
                       for n in entry.rw_names]))
            if host:
                # transfer only the crossing vars; run the op eagerly —
                # callbacks execute immediately (host-side) outside of jit.
                # Pin the tiny surrounding math to the CPU backend; where
                # JAX_PLATFORMS left 'cpu' unregistered, plain eager on the
                # default device (the callback itself runs on host either
                # way)
                seg_feed = {n: np.asarray(v) for n, v in seg_feed.items()}
                rec.ro = tuple(map(np.asarray, rec.ro))
                rec.rw = tuple(map(np.asarray, rec.rw))
                try:
                    guard = jax.default_device(
                        jax.local_devices(backend='cpu')[0])
                except RuntimeError:
                    guard = contextlib.nullcontext()

                def _host_dispatch():
                    resilience.maybe_fault('host_relay')
                    with guard:
                        return entry.fn._fn(seg_feed, rec.ro, rec.rw,
                                            key_arr)

                def _boundary_fault(e):
                    # host segments run callbacks with SIDE EFFECTS
                    # (py_func appending to files, print): a failure
                    # after the callback ran is not safely re-invocable.
                    # Only boundary-injected faults — raised BEFORE the
                    # segment executed — retry; real mid-segment
                    # transients propagate.
                    return isinstance(e, resilience.InjectedFault) \
                        and e.transient
                try:
                    fetches, new_state = _host_dispatch()
                except Exception as e:  # noqa: BLE001 — classified inside
                    fetches, new_state = resilience.retry_after(
                        e, _host_dispatch, site='host_relay',
                        retryable=_boundary_fault)
                # host work, not device-productive: booked nowhere
                times = None
            else:
                # device segments contribute busy time (no flops: the
                # per-segment clones don't register analytics)
                with coldstart.stage('first_run', sub, *_RUN_COMPILE) \
                        if fresh else contextlib.nullcontext():
                    fetches, new_state, times = self._call(
                        entry, seg_feed, rec, key_arr)
            # the crossing values go on as they are (a sparse gradient
            # stays sparse for the part that applies it)
            self._commit(scope, entry, program, rec, fetches, new_state,
                         times)
            val_env.update(zip(entry.fetch_names, fetches))
            lod_env.update(entry.lod_out)

        from .core.selected_rows import SelectedRows
        out = [val_env[n] if n in val_env
               else self._state_value(scope, n, program)
               for n in fetch_names]
        return (types.SimpleNamespace(fetch_names=fetch_names,
                                      lod_out=lod_env, to_host=np.asarray),
                [v.to_dense() if isinstance(v, SelectedRows) else v
                 for v in out], None)

    # ------------------------------------------------------------------
    def run_fused(self, program=None, feed_list=None, fetch_list=None,
                  scope=None, return_numpy=True, steps=None,
                  donate=None, _prepared=None):
        """Run len(feed_list) consecutive steps in ONE compiled call.

        The step function is iterated on-device with lax.fori_loop over the
        pre-stacked feed batches (uploaded once), so host->device launch
        latency and the per-step host work are paid once per K steps
        instead of per step. This is the
        TPU-native analog of the reference amortization knobs
        (ExecutionStrategy.num_iteration_per_drop_scope,
        details/execution_strategy.h:22; AsyncExecutor's many-iterations-
        per-dispatch loop, framework/async_executor.cc:236).

        feed_list: list of K feed dicts with identical names/shapes/dtypes
        — ragged (array, lod) feeds may VARY their LoD/shape across the
        staged batches: the list is split into maximal consecutive
        same-LoD segments (order-preserving, so the training trajectory
        is untouched) and each segment scans as its own fused call.
        Compiles are cached per (shape, segment length), so a stream
        sorted bucket-major (reader/bucketing.py) fuses at full length,
        while a heavily interleaved stream degrades gracefully toward
        per-step execution (correct, but without the fusion win — group
        by bucket first when throughput matters). — OR a pre-stacked
        {name: array[K, ...]} dict: pass device-resident (jax.device_put)
        stacked arrays to avoid re-uploading large feeds on every call
        (the input-pipeline staging an async py_reader would do). Returns
        the LAST step's fetches; all K state updates land in the scope.
        `steps` (run more scan iterations than staged batches, cycling
        them) requires a uniform-LoD feed_list. `donate` overrides the
        donation default for this call only, like Executor.run.
        """
        if not feed_list:
            return []
        with monitor.timed_span('run_fused', 'executor_run_fused_seconds'):
            monitor.inc('executor_run_fused_total')
            return self._run_fused_impl(program, feed_list, fetch_list,
                                        scope, return_numpy, steps,
                                        donate, _prepared)

    def _run_fused_impl(self, program, feed_list, fetch_list, scope,
                        return_numpy, steps, donate_override, _prepared):
        import jax
        from jax import lax
        if program is None:
            program = default_main_program()
        if scope is None:
            scope = global_scope()
        lods0 = {}
        if isinstance(feed_list, dict):
            stacked = dict(feed_list)
            # host-resident stacks upload on this call; device jax.Arrays
            # (the documented staging pattern) don't re-cross the host.
            # The list path below counts its bytes in _prepare_feed.
            host = sum(int(v.nbytes) for v in stacked.values()
                       if isinstance(v, np.ndarray))
            if host:
                monitor.inc('feed_host_bytes', host)
            k_steps = int(next(iter(stacked.values())).shape[0])
            # metadata-only stand-ins for one staged batch: feed0 exists
            # for the cache key (shape/dtype) and key-set checks; slicing
            # the device arrays here would dispatch a per-leaf device op
            # on every steady-state call
            feed0 = {kk: _FeedSpec(tuple(np.shape(v))[1:],
                                   getattr(v, 'dtype', None))
                     for kk, v in stacked.items()}
        else:
            prepared = _prepared if _prepared is not None else [
                self._prepare_feed(program, f or {}) for f in feed_list]
            lods0 = prepared[0][1]
            if any(lods != lods0 for _, lods in prepared):
                # mixed-LoD stream: split into maximal consecutive
                # same-LoD segments and fuse each separately — order is
                # preserved, so K state updates land exactly as a
                # per-step loop would apply them
                if steps:
                    raise ValueError(
                        "run_fused(steps=...) cycles the staged batches "
                        "and requires one uniform LoD; omit steps for a "
                        "mixed-LoD stream (segments run at their own "
                        "lengths)")
                out = []
                seg_lo = 0
                for i in range(1, len(feed_list) + 1):
                    if i == len(feed_list) or \
                            prepared[i][1] != prepared[seg_lo][1]:
                        # chunk the segment to power-of-two lengths
                        # (largest first): compiles cache per (shape,
                        # chunk length), so this bounds entries per LoD
                        # shape to O(log K) across arbitrary streams
                        # instead of one per distinct segment length
                        lo = seg_lo
                        while lo < i:
                            size = 1 << ((i - lo).bit_length() - 1)
                            # recurse through _run_fused_impl, NOT the
                            # public wrapper: one logical run_fused call
                            # counts once, and segment windows must not
                            # nest duplicate spans/latency observations
                            out = self._run_fused_impl(
                                program, feed_list[lo:lo + size],
                                fetch_list, scope, return_numpy, None,
                                donate_override, prepared[lo:lo + size])
                            lo += size
                        seg_lo = i
                return out
            feeds = [f for f, _ in prepared]
            k_steps = len(feeds)
            stacked = {name: np.stack([np.asarray(f[name]) for f in feeds])
                       for name in feeds[0]}
            feed0 = feeds[0]
        static_names = self._static_feed_names(program)
        if any(n in feed0 for n in static_names):
            raise ValueError(
                "run_fused cannot scan shape-bearing static feeds %r"
                % sorted(static_names & set(feed0)))
        fetch_names = [v.name if isinstance(v, Variable) else v
                       for v in (fetch_list or [])]

        static_lods = self._static_lods(scope, lods0)
        n_steps = int(steps) if steps else k_steps
        cache_key = self._entry_key(program, feed0, static_lods, (),
                                    fetch_names, donate_override,
                                    ('fused', k_steps, n_steps))

        def _build_fused():
            read, written = lowering.analyze_state(program, fetch_names)
            needed = self._read_before_write(program, read, written,
                                             set(feed0), fetch_names)
            lod_out = {}
            fn, ro_names, rw_names = lowering.build_fn(
                program, fetch_names, needed, written,
                static_lods=static_lods, lod_out=lod_out)

            def fused(stacked_feed, ro, rw, base_key):
                # carry: ONE merged state dict (all written persistables,
                # seeded with the read-write values) + last fetches.
                # new_state ⊇ rw, so the rw slice the step consumes is a
                # subset view — carrying rw and ns as separate dicts (the
                # round-3 layout) doubled the while-loop tuple and cost
                # ~1300 loop-carry copies per iteration in the compiled
                # body (measured: resnet50 fused step 190 ms vs ~25 ms
                # for the same math outside the old carry layout)
                feed0 = {kk: v[0] for kk, v in stacked_feed.items()}
                (f0, ns0) = jax.eval_shape(
                    fn, feed0, ro, rw, jax.random.PRNGKey(0))
                # seed the carry at the step function's fixed-point dtypes
                rw = {kk: jnp.asarray(v, ns0[kk].dtype) if kk in ns0
                      else v for kk, v in rw.items()}
                rw_keys = set(rw)

                def body(i, carry):
                    st, _ = carry
                    feed_i = {kk: lax.dynamic_index_in_dim(
                        v, jnp.mod(i, k_steps), 0, keepdims=False)
                              for kk, v in stacked_feed.items()}
                    key_i = jax.random.fold_in(base_key, i)
                    fetches_i, ns = fn(
                        feed_i, ro, {kk: st[kk] for kk in rw_keys}, key_i)
                    st_next = {kk: ns.get(kk, st[kk]) for kk in st}
                    return st_next, tuple(fetches_i)

                st_init = {kk: jnp.zeros(sp.shape, sp.dtype)
                           for kk, sp in ns0.items()}
                st_init.update(rw)
                init_f = tuple(jnp.zeros(sp.shape, sp.dtype) for sp in f0)
                st_out, fetches = lax.fori_loop(
                    0, n_steps, body, (st_init, init_f))
                return fetches, {kk: st_out[kk] for kk in ns0}

            # Donation default ON (see _donation_enabled): parameter updates
            # alias their input buffers instead of doubling peak HBM. The
            # state goes in flat like every entry's, under the window's
            # own name: `flat` is jitted anew
            call = lowering.StateCallable(fused, ro_names, rw_names, program,
                                          cache_key[-1])
            lowering.name_after(call._fn, program, '_fused')
            call.flat = jax.jit(call._fn, donate_argnums=call._donate)
            # fused analytics register the scan; XLA cost analysis counts
            # the while BODY once (measured: flops identical for 4- and
            # 8-step scans), so the registered flops are per-step and
            # goodput multiplies by the dispatch's n_steps
            return _CompiledEntry(call, fetch_names, written, program,
                                  lod_out, kind='fused', steps=n_steps)
        # the step, outside a run's phases: a window is no run
        entry, fetches, _ = self._step(
            scope, program, lambda: self._find(
                cache_key, program, _build_fused, book=()) + (stacked,),
            phase=_no_phase, book=())
        return [np.asarray(f) for f in fetches] if return_numpy \
            else list(fetches)

    # ------------------------------------------------------------------
    def bind(self, program, feed, fetch_list=None, scope=None, donate=None):
        """Prepare a FIXED-SIGNATURE run for a hot dispatch loop
        (serving/generate.py's token decode): the entry looked up or built
        and cached as `run()` does, the `BoundProgram` on it, and one run —
        a run like any other, its one call the handle's first. The
        handle's calls skip the per-run key work — feed preparation,
        fingerprint/signature hashing, cache lookup, spans and phases.

        Contract: every subsequent call must feed the SAME names, shapes
        and dtypes as `feed` (the bound executable is never re-keyed); the
        program must be host-op-free (no segmented execution) and not
        under op-attribution profiling. Programs without RNG-consuming ops
        reuse one PRNG key across calls — is_test decode programs; a
        program WITH rng ops derives a fresh per-call key exactly like
        run(). Fault injection and retry at the 'run' site behave as in
        run(); `donate` resolves once at bind time."""
        if scope is None:
            scope = global_scope()
        if analysis.profile_ops_active() or (
                os.environ.get('PADDLE_SEGMENT_HOST_OPS') == '1'
                and self._host_splittable(program)):
            raise RuntimeError(
                "Executor.bind: no one entry runs this (program, feed, "
                "fetch) signature — bind() supports host-op-free programs "
                "outside profile_ops mode only (a run() goes through a "
                "different execution path)")
        # needs_rng may be a static per-op-instance predicate (e.g.
        # fused_ffn_tail: only a train-mode op with live dropout draws a
        # key) — decode programs keep the single-PRNGKey fast path
        needs_rng = any(
            has_op(op.type) and _op_needs_rng(get_op(op.type), op)
            for block in program.blocks for op in block.ops)
        made = []

        def find():
            """The entry `run()` would look up or build, and the handle
            on it: what is compiled and run first is the entry every
            later call dispatches."""
            feed2, fetch_names, static_feed, static_lods = \
                self._prepare_run_inputs(program, feed, scope, fetch_list)
            key = self._entry_key(program, feed2, static_lods, static_feed,
                                  fetch_names, donate)
            entry, since = self._find(
                key, program, lambda: self._build_entry(
                    program, feed2, fetch_names, static_lods, static_feed,
                    key[-1]))
            made.append(BoundProgram(self, entry, program, scope, needs_rng,
                                     feed2))
            return made[0]._entry, since, feed2
        # a run like any other (`_run_plain`), its one call the handle's
        with trace_mod.step_scope('step'):
            with monitor.timed_span('run', 'executor_run_seconds'):
                monitor.inc('executor_run_total')
                entry, fetches, _ = self._step(scope, program, find)
                bound, = made
                # the leaves the first call took are the handle's first
                # staging, no restage: the scope's record holds them too
                held = scope._held.get(entry)
                bound._ro = held.ro if held is not None else None
                # that call was a run's, booked so; the handle's are its own
                entry.kind = 'bound'
                bound.first_out = self._fetch(entry, fetches, True)
        return bound

    # ------------------------------------------------------------------
    def precompile(self, program=None, feed_spec=None, fetch_list=None,
                   scope=None, donate=None):
        """AOT lowered-artifact reuse: lower + XLA-compile the (program,
        feed signature, fetch set) entry ahead of traffic, keyed by the
        SAME fingerprint compile cache ``run()`` uses — the first real
        dispatch then hits both the entry cache and the jitted
        executable. Unlike a warmup ``run()``, nothing observable
        happens: the compile executes against zero-filled feeds and
        COPIES of the scope's read-write state (donation consumes the
        copies), the scope is never updated, and the PRNG run counters
        do not advance — a precompiled training program replays the
        exact trajectory it would have without precompile.

        ``feed_spec``: {name: array | (shape, dtype) | ShapeDtypeStruct}.
        Pass real arrays for shape-bearing (static) feeds — zeros bind as
        the trace-time constant otherwise. Returns {'compiled', 'seconds',
        'cached'}; a second precompile (or any run) of the same signature
        is a cache hit with seconds ≈ 0 — the contract
        tools/warmfarm.py builds the cross-worker warmup farm on."""
        if program is None:
            program = default_main_program()
        if scope is None:
            scope = global_scope()
        if analysis.profile_ops_active():
            return {'compiled': False, 'cached': False, 'seconds': 0.0,
                    'skipped': 'profile_ops'}
        feed = _feed_from_spec(feed_spec)
        feed, fetch_names, static_feed, static_lods = \
            self._prepare_run_inputs(program, feed, scope, fetch_list,
                                     count=False)
        if any(op.type in _HOST_SEGMENT_OPS for op in
               program.global_block().ops):
            # an AOT pass over a host-op program would have to execute
            # its host callbacks on fabricated data (and under
            # PADDLE_SEGMENT_HOST_OPS=1 it compiles per segment inside
            # run()) — not a warmup farm's contract
            return {'compiled': False, 'cached': False, 'seconds': 0.0,
                    'skipped': 'host_ops'}
        key = self._entry_key(program, feed, static_lods, static_feed,
                              fetch_names, donate, record=False)
        monitor.inc('precompile_total')
        entry, since = self._find(
            key, program, lambda: self._build_entry(
                program, feed, fetch_names, static_lods, static_feed,
                key[-1]), book=())
        if since is None:
            return {'compiled': False, 'cached': True, 'seconds': 0.0}
        # rw state is DONATED by the compiled fn: hand it throwaway
        # copies so the scope's live buffers survive precompilation
        rec = _Held(
            self._walk(scope, entry, program, entry.ro_names), None,
            tuple([jnp.array(v, copy=True) for v in self._walk(
                scope, entry, program, entry.rw_names, False)]))
        frame = coldstart.compile_frame(program, since=since)
        # the outputs are let go: the scope stays untouched
        _first_call(frame, entry, feed, rec, _run_key(program.random_seed,
                                                      0, 0))
        return {'compiled': True, 'cached': False,
                'seconds': round(frame.seconds, 4)}

    # ------------------------------------------------------------------
    def explain(self, program=None, feed=None, fetch_list=None, scope=None,
                memory=True):
        """Compile-time cost/memory report for `program` at this feed
        signature — WITHOUT executing it (state shapes are read from the
        scope as metadata; nothing is uploaded or run).

        Returns a dict: ``flops``, ``transcendentals``,
        ``bytes_accessed`` (XLA HloCostAnalysis), ``argument_bytes`` /
        ``output_bytes`` / ``temp_bytes`` / ``alias_bytes`` /
        ``peak_bytes`` (XLA buffer assignment; ``memory=False`` skips
        them and the extra XLA compile they cost), plus ``op_count`` /
        ``ops`` / ``fingerprint``. The compiled trace is shared with the
        run cache, so ``explain`` before ``run`` prices one trace, not
        two. CLI twin: ``tools/costreport.py``."""
        return analysis.explain_program(self, program, feed=feed,
                                        fetch_list=fetch_list, scope=scope,
                                        memory=memory)

    # ------------------------------------------------------------------
    def _state_ref(self, scope, name):
        """Scope value for aval/metadata purposes only — no device upload,
        no caching, same not-initialized error contract as _state_value."""
        v = scope.get(name)
        if v is None:
            raise RuntimeError(
                "persistable variable %r is not initialized in the scope — "
                "run the startup program first (reference: EnforceNotMet "
                "'Var is not initialized')" % name)
        return v

    def _state_value(self, scope, name, program, cache=True):
        v = self._state_ref(scope, name)
        if isinstance(v, np.ndarray) or np.isscalar(v):
            # cache the device array back into the scope: read-only state
            # (inference predictors, frozen params) is never rewritten by
            # new_state, and re-converting per call re-UPLOADS the whole
            # tensor every run (~100 MB for ResNet-50's weights loaded
            # from disk as numpy).
            # Only when the conversion is lossless: x64-disabled jax
            # narrows int64/float64, and that narrowed dtype must not
            # leak back into the scope (save_persistables would then
            # checkpoint the narrowed array).
            # set-up's `place` stage: the upload's dispatch (the copy
            # itself is not waited for), where it is a once-only move —
            # the scope keeps the device copy below, or the run rebinds
            # the name (cache=False). A value that cannot be cached is
            # uploaded every call, a cost of the steady path and no
            # set-up: it opens no frame
            if not cache or (
                    isinstance(v, np.ndarray) and v.base is None and
                    jax.dtypes.canonicalize_dtype(v.dtype) == v.dtype):
                with coldstart.stage('place', program):
                    dv = jnp.asarray(v)
            else:
                dv = jnp.asarray(v)
            if cache and isinstance(v, np.ndarray) and dv.dtype == v.dtype \
                    and dv.shape == v.shape:
                # The scope now answers reads from the device copy, so a
                # later IN-PLACE write through the caller's numpy alias
                # would be silently dropped. Freeze the caller's buffer so
                # that write raises loudly instead (rebind via scope.set /
                # tensor.set to update). A view (v.base is not None) can't
                # be frozen against writes through its base — skip caching
                # and keep re-converting those. (Known gap: a view the
                # CALLER created before this freeze stays writable —
                # numpy does not propagate writeable=False to existing
                # views — so writes through such an alias are still
                # silently dropped.) Callers pass cache=False
                # for read-AND-written names: new_state rebinds those
                # right after the run, so the scope never aliases the
                # caller's buffer past the call and freezing it would
                # break legitimate host-side reuse of an init buffer.
                if v.base is None:
                    try:
                        v.flags.writeable = False
                    except ValueError:
                        return dv
                    scope.update({name: dv})
            return dv
        return v

    @staticmethod
    def _read_before_write(program, read, written, feed_names, fetch_names):
        """A persistable var written earlier in the program than any read
        (e.g. created by fill_constant in the same program) need not come
        from the scope."""
        first_write = {}
        first_read = {}
        # walk ops in EXECUTION order: sub-block ops are visited at their
        # parent control-flow op's position (a later top-level op must get
        # a later index than reads inside an earlier while/cond body)
        counter = [0]

        def _walk(block, in_sub):
            for op in block.ops:
                idx = counter[0]
                counter[0] += 1
                names_in = list(op.input_arg_names)
                if op.type == 'backward':
                    names_in += list(op.attr('wrt_names'))
                # writes inside control-flow sub-blocks are conditional:
                # the var's prior value may survive (untaken branch /
                # zero-trip loop), so they count as reads as well
                if in_sub:
                    names_in += list(op.output_arg_names)
                for n in names_in:
                    first_read.setdefault(n, idx)
                for n in op.output_arg_names:
                    first_write.setdefault(n, idx)
                sub = op.attr('sub_block', None)
                if sub is not None:
                    _walk(program.block(int(sub)), True)

        _walk(program.global_block(), False)
        idx = counter[0]
        for n in fetch_names:
            first_read.setdefault(n, idx)
        needed = []
        for n in read:
            if n in feed_names:
                continue
            if n in first_write and first_write[n] < first_read.get(n, idx + 1):
                continue
            needed.append(n)
        return needed
