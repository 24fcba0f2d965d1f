"""SPMD data-parallel execution of a Program over a mesh.

This is the TPU-native ParallelExecutor (reference
framework/parallel_executor.cc:184 + details/multi_devices_graph_pass.cc):
instead of cloning per-device op graphs and inserting NCCL AllReduce
op-handles (multi_devices_graph_pass.cc:515), we jit the SAME lowered program
with the feed batch dimension sharded over mesh axis 'data' and parameters
replicated. The XLA SPMD partitioner splits every op across devices and
inserts psum/reduce-scatter collectives over ICI for the gradient reductions —
semantically identical to AllReduce mode with CoeffNumDevice scaling (the
global-batch mean IS the 1/N-scaled allreduce).
"""
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import coldstart
from .. import monitor
from ..core import lowering
from ..framework import Variable
from .mesh import data_mesh

__all__ = ['DataParallelRunner', 'place_state']


class _Entry(object):
    __slots__ = ('fn', 'ro_names', 'rw_names', 'written', 'feed_shardings',
                 'state_shardings', 'lod_out', '__weakref__')

    def __init__(self, fn, ro_names, rw_names, written, feed_shardings,
                 state_shardings, lod_out=None):
        self.fn = fn
        self.ro_names = ro_names
        self.rw_names = rw_names
        self.written = written
        self.feed_shardings = feed_shardings
        self.state_shardings = state_shardings
        self.lod_out = lod_out if lod_out is not None else {}


def place_state(scope, state, shardings, program='program'):
    """Lay single-process `state` ({name: array}) out as `shardings`
    ({name: NamedSharding}) before it is handed to the sharded jit, ONCE:
    every moved array is rebound into the scope, so later runs (and
    read-only state such as lr scalars or frozen weights, which new_state
    never rebinds) find it in place. Two kinds of state arrive elsewhere:
    uncommitted arrays the startup program left on the default device, and
    arrays COMMITTED to another device set — restored by
    checkpoint.load_checkpoint(mesh=...) onto a shrunken post-preemption
    mesh, or left over from a larger one — which jit would refuse
    (counted in spmd_state_migrated_total). jit could move the first kind
    itself, but a step-1 input on one device and a step-2 input on the
    mesh are two different lowerings to it: the same step compiled twice,
    unseen by compile_cache_miss. A move is set-up's `place` stage for
    `program` (coldstart.py: the host's time in the call, the copy is not
    waited for); a leaf found in place opens nothing."""
    out = {}
    for n, v in state.items():
        target = shardings[n]
        if isinstance(v, jax.Array) and v.is_fully_addressable \
                and not v.sharding.is_equivalent_to(target, v.ndim):
            if getattr(v, '_committed', False):
                monitor.inc('spmd_state_migrated_total')
            with coldstart.stage('place', program):
                v = jax.device_put(v, target)
            scope.set(n, v)
        out[n] = v
    return out


class DataParallelRunner(object):
    def __init__(self, program, loss_name=None, build_strategy=None,
                 places=None, mesh=None):
        self._program = program
        self._loss_name = loss_name
        self._build_strategy = build_strategy
        self._mesh = mesh if mesh is not None else data_mesh(
            len(places) if places else None)
        self._cache = {}
        self._run_counter = 0

    @property
    def num_devices(self):
        return int(np.prod(list(self._mesh.shape.values())))

    def _strategy_knobs(self):
        """Map BuildStrategy onto the SPMD compile (reference
        details/build_strategy.h:34-96). Unsupported combinations error
        loudly instead of being silently ignored."""
        from ..compiler import BuildStrategy
        bs = self._build_strategy
        lower_params = {}
        reduce_mode = False
        if bs is not None:
            gss = bs.gradient_scale_strategy
            if gss == BuildStrategy.GradientScaleStrategy.One:
                # reference: loss grad seeded with 1 per device instead of
                # 1/N; with our global-batch-mean formulation that is a
                # factor of num_devices on every gradient
                lower_params['loss_grad_scale'] = float(self.num_devices)
            elif gss == BuildStrategy.GradientScaleStrategy.Customized:
                raise NotImplementedError(
                    "BuildStrategy.GradientScaleStrategy.Customized needs a "
                    "user-provided loss@GRAD feed, which the SPMD runner "
                    "does not support — scale the loss in the program "
                    "instead")
            reduce_mode = (bs.reduce_strategy ==
                           BuildStrategy.ReduceStrategy.Reduce)
        return lower_params, reduce_mode

    def _state_sharding(self, program, name, reduce_mode, mesh):
        """Reduce mode = parameters/optimizer state sharded over 'data'
        (the ZeRO-style TPU analog of reference ReduceSSAGraphBuilder:
        each grad reduced to one owner + param updated there; XLA inserts
        reduce_scatter for the grads and all_gathers for the forward)."""
        if not reduce_mode:
            return NamedSharding(mesh, P())
        v = program.global_block()._find_var_recursive(name)
        ndev = self.num_devices
        shape = tuple(v.shape) if v is not None and v.shape else ()
        # shard the LARGEST axis divisible by the device count (reference
        # ReduceSSAGraphBuilder balances whole params across devices; the
        # sharded analog slices whichever axis divides evenly — dim0 for
        # embeddings, dim1 for e.g. [in, out] fc weights with odd in)
        best = None
        for ax, dim in enumerate(shape):
            if dim and dim > 0 and dim % ndev == 0 and \
                    (best is None or dim > shape[best]):
                best = ax
        if best is not None:
            spec = [None] * len(shape)
            spec[best] = 'data'
            return NamedSharding(mesh, P(*spec))
        size = int(np.prod([d for d in shape if d])) if shape else 0
        if size >= 1024:
            import warnings
            warnings.warn(
                "Reduce (ZeRO) mode: variable %r shape %s has no axis "
                "divisible by %d devices — replicating it (no per-device "
                "memory saving for this variable; pad a dimension to a "
                "multiple of the device count to shard it)"
                % (name, shape, ndev), RuntimeWarning, stacklevel=3)
        return NamedSharding(mesh, P())

    def _compile(self, feed, fetch_names, feed_lods=None):
        program = self._program
        read, written = lowering.analyze_state(program, fetch_names)
        from ..executor import Executor
        needed = Executor._read_before_write(program, read, written,
                                             set(feed), fetch_names)
        lower_params, reduce_mode = self._strategy_knobs()
        bs = self._build_strategy
        if bs is not None and getattr(bs, 'debug_graphviz_path', ''):
            from ..debugger import draw_block_graphviz
            draw_block_graphviz(program, bs.debug_graphviz_path)
        feed_lods = dict(feed_lods or {})
        lod_out = {}
        fn, ro_names, rw_names = lowering.build_fn(
            program, fetch_names, needed, written,
            static_lods=feed_lods, lod_out=lod_out,
            lower_params=lower_params)
        mesh = self._mesh
        repl = NamedSharding(mesh, P())
        batch_sharded = NamedSharding(mesh, P('data'))
        # ragged (LoD) feeds replicate: rows are per-sequence, not evenly
        # splittable over devices (reference SplitLoDTensor splits by
        # instance at feed time; the TPU path is bucket+pad to dense —
        # reader/bucketing.py — when scaling matters)
        feed_shardings = {k: (repl if k in feed_lods else batch_sharded)
                          for k in feed}
        state_shard = {n: self._state_sharding(program, n, reduce_mode,
                                               mesh)
                       for n in set(ro_names) | set(rw_names) | set(written)}
        # the state goes in flat, in the SORTED order in which jit
        # flattens a dict (lowering.StateCallable: the compiled program's
        # parameter list is what it was when the state went in by name),
        # so a run hands on the tuples the run before it left; called or
        # lowered with the state by name, it lines the leaves up itself.
        # Its `flat` is jitted anew, with the mesh's shardings
        call = lowering.StateCallable(fn, ro_names, rw_names, program, True)
        in_shardings = (
            feed_shardings,
            tuple([state_shard[n] for n in call.ro_names]),
            tuple([state_shard[n] for n in call.rw_names]),
            repl,
        )
        out_shardings = (None, {n: state_shard[n] for n in written})
        call.flat = jax.jit(call._fn, in_shardings=in_shardings,
                            out_shardings=out_shardings,
                            donate_argnums=(2,))
        return _Entry(call, call.ro_names, call.rw_names, written,
                      feed_shardings, state_shard, lod_out)

    def run(self, executor, feed, fetch_list, scope, return_numpy):
        """One step, in the phases Executor.run has
        (executor_run_phase_seconds_total{phase}): prepare — feed
        preparation, the signature, the state (what this entry's last run
        left, `executor._carried_state`, or every leaf looked up and
        found in place), the run key; dispatch — the sharded call (a
        signature's first is set-up's frame, and the `compile` phase);
        commit — the scope rebind, the record for the next run, the
        donated inputs let go, LoDs; fetch — the wait for the device.
        Runs are counted where the CompiledProgram delegates
        (executor_run_total, compiler.py)."""
        from ..executor import (_run_phase, _compile_frame, _carry_state,
                                global_scope)
        if scope is None:
            scope = global_scope()
        with _run_phase('prepare'):
            entry, feed, ro, rw, key_arr, fetch_names, since = \
                self._prepare(executor, feed, fetch_list, scope)
        flat = entry.fn.flat
        program = self._program
        from . import api as _papi
        prev, _papi._ACTIVE_MESH = _papi._ACTIVE_MESH, self._mesh
        _, reduce_mode = self._strategy_knobs()
        prev_spec = _papi._ACTIVE_PARAM_SPEC
        # fused units partition state by its actual placement: replicated
        # in plain DP, the ZeRO-style reduce-mode spec otherwise
        _papi._ACTIVE_PARAM_SPEC = (
            lambda n: self._state_sharding(program, n, reduce_mode,
                                           self._mesh).spec)
        try:
            with self._mesh:
                if since is not None:
                    # like the serial executor: jax.jit is lazy, the XLA
                    # compile happens inside the FIRST call — compile wall
                    # time must cover it, not just the jit construction
                    with _compile_frame(program, since=since):
                        fetches, new_state = flat(feed, ro, rw, key_arr)
                else:
                    with _run_phase('dispatch'):
                        fetches, new_state = flat(feed, ro, rw, key_arr)
        finally:
            _papi._ACTIVE_MESH = prev
            _papi._ACTIVE_PARAM_SPEC = prev_spec
        with _run_phase('commit'):
            from .. import flags as _flags
            if _flags.get_flags('check_nan_inf'):
                from ..executor import _check_nan_inf
                _check_nan_inf(
                    {n: self._fetch_to_host(v)
                     for n, v in new_state.items()},
                    dict(zip(fetch_names,
                             [self._fetch_to_host(f) for f in fetches])))
            if _flags.get_flags('benchmark'):
                with _run_phase('fetch'):
                    jax.block_until_ready(fetches)
            scope.update(new_state)
            if jax.process_count() == 1:
                _carry_state(scope, entry, ro, new_state)
            # the donated inputs go here, while the device is busy, not
            # as the frame exits behind the fetch's wait
            del ro, rw
            if entry.lod_out or scope._lods:
                for n in new_state:
                    lod = entry.lod_out.get(n)
                    if lod:
                        scope._lods[n] = lod
                    else:
                        scope._lods.pop(n, None)
        if not return_numpy:
            return list(fetches)
        from ..executor import _fetched
        with _run_phase('fetch'):
            out = []
            for n, f in zip(fetch_names, fetches):
                host = self._fetch_to_host(f)
                lod = entry.lod_out.get(n)
                out.append(_fetched(host, lod) if lod else host)
            return out

    def _prepare(self, executor, feed, fetch_list, scope):
        """Everything of a run ahead of the sharded call: (entry, feed,
        the read-only and the read-written leaves in the entry's order,
        key, fetch names, and — for a
        signature's first run, whose entry was made here — when its
        making began)."""
        program = self._program
        feed, feed_lods = executor._prepare_feed(program, feed or {})
        # LoD-carrying scope state binds statically, like the serial
        # executor (executor.py scope_lods handling)
        from ..core.lod import normalize_lod as _nl
        scope_lods = {n: _nl(l) for n, l in
                      getattr(scope, '_lods', {}).items() if l}
        static_lods = dict(scope_lods)
        static_lods.update(feed_lods)
        fetch_names = [v.name if isinstance(v, Variable) else v
                       for v in (fetch_list or [])]
        nproc = jax.process_count()
        # under multi-host, each process feeds its LOCAL batch shard
        # (reference: each trainer reads its own data slice); divisibility
        # is per local device count
        ndev = self.num_devices // nproc if nproc > 1 else self.num_devices
        for k, v in feed.items():
            if k in feed_lods:
                continue          # ragged feeds replicate (see _compile)
            if v.shape and v.shape[0] % max(ndev, 1) != 0:
                raise ValueError(
                    "feed %r batch %d not divisible by %d mesh devices"
                    % (k, v.shape[0], ndev))
        key = (program._uid, program._version,
               executor._feed_signature(feed, static_lods),
               tuple(fetch_names))
        entry = self._cache.get(key)
        since = None
        if entry is None:
            monitor.inc('compile_cache_miss')
            since = time.perf_counter()
            from ..executor import _wire_persistent_cache, _RUN_COMPILE
            _wire_persistent_cache()
            with coldstart.stage('trace', program, *_RUN_COMPILE):
                entry = self._compile(feed, fetch_names,
                                      feed_lods=static_lods)
            self._cache[key] = entry
        else:
            monitor.inc('compile_cache_hit')

        from ..executor import _carried_state, _run_key, _next_program_run
        # one process: what this entry's last run on the scope left, if
        # nothing wrote the scope since — in place already, the entry's
        # own outputs under its `out_shardings`
        state = _carried_state(scope, entry) if nproc == 1 else None
        if state is None:
            ro_state = {n: executor._state_value(scope, n, program)
                        for n in entry.ro_names}
            rw_state = {n: executor._state_value(scope, n, program)
                        for n in entry.rw_names}
            if nproc == 1:
                ro_state = place_state(scope, ro_state,
                                       entry.state_shardings, program)
                rw_state = place_state(scope, rw_state,
                                       entry.state_shardings, program)
            else:
                # assemble global arrays from per-process host-local data
                # (feeds: local batch shard; state: every process holds
                # the full value — identical init from the same seed)
                def _globalize_feed(sharding, v):
                    if isinstance(v, jax.Array) \
                            and not v.is_fully_addressable:
                        return v
                    return jax.make_array_from_process_local_data(
                        sharding, np.asarray(v))

                def _globalize_state(sharding, v):
                    if isinstance(v, jax.Array) \
                            and not v.is_fully_addressable:
                        return v      # already a global array from last step
                    arr = np.asarray(v)
                    return jax.make_array_from_callback(
                        arr.shape, sharding, lambda idx: arr[idx])

                feed = {k: _globalize_feed(entry.feed_shardings[k], v)
                        for k, v in feed.items()}
                ro_state = {n: _globalize_state(entry.state_shardings[n], v)
                            for n, v in ro_state.items()}
                rw_state = {n: _globalize_state(entry.state_shardings[n], v)
                            for n, v in rw_state.items()}
            state = tuple(ro_state.values()), tuple(rw_state.values())
        ro, rw = state
        self._run_counter += 1
        key_arr = _run_key(program.random_seed, _next_program_run(program),
                           self._run_counter)
        if nproc > 1:
            # the PRNG key must be a global replicated array too (every
            # process derives the identical value from the shared seed /
            # run counters)
            karr = np.asarray(key_arr)
            key_arr = jax.make_array_from_callback(
                karr.shape, NamedSharding(self._mesh, P()),
                lambda idx: karr[idx])
        return entry, feed, ro, rw, key_arr, fetch_names, since

    @staticmethod
    def _fetch_to_host(f):
        """Host view of a fetch. Multi-host: replicated fetches (losses,
        metrics) give the full value; batch-sharded fetches give this
        process's local rows, like each reference trainer seeing its own
        split (parallel_executor.cc FeedAndSplitTensorIntoLocalScopes)."""
        if not isinstance(f, jax.Array) or f.is_fully_addressable:
            return np.asarray(f)
        uniq = {}
        for s in f.addressable_shards:      # dedupe replicas by index
            uniq.setdefault(s.index, s.data)
        if len(uniq) == 1:
            # replicated value, or the single shard this process owns
            return np.asarray(next(iter(uniq.values())))
        idxs = list(uniq)
        varying = [d for d in range(len(f.shape))
                   if len({(ix[d].start, ix[d].stop) for ix in idxs}) > 1]
        if len(varying) != 1:
            raise ValueError(
                "multi-host fetch is sharded over %d axes; fetch a "
                "replicated value (e.g. the mean loss) or keep outputs "
                "sharded with return_numpy=False" % len(varying))
        ax = varying[0]
        ordered = sorted(uniq.items(),
                         key=lambda kv: kv[0][ax].start or 0)
        return np.concatenate([np.asarray(v) for _, v in ordered], ax)
