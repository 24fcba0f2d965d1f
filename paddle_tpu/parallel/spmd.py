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
import numpy as np
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import coldstart
from .. import monitor
from ..core import lowering
from .mesh import data_mesh

__all__ = ['DataParallelRunner', 'place_state']


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


def fetch_to_host(f):
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


def _global(sharding, v):
    """One process' whole copy of a value as its part of the global
    array (every process holds the full value — state: identical init
    from the same seed; the run key: the shared seed and counters)."""
    if isinstance(v, jax.Array) and not v.is_fully_addressable:
        return v          # already a global array from last step
    arr = np.asarray(v)
    return jax.make_array_from_callback(arr.shape, sharding,
                                        lambda idx: arr[idx])


def global_feed(shardings, feed):
    """Each process' LOCAL batch shard as its rows of the global feed
    (reference: each trainer reads its own data slice)."""
    return {k: v if isinstance(v, jax.Array) and not v.is_fully_addressable
            else jax.make_array_from_process_local_data(shardings[k],
                                                        np.asarray(v))
            for k, v in feed.items()}


def sharded_entry(program, mesh, feed_names, fetch_names, static_lods,
                  feed_sharding, state_sharding, lower_params=None):
    """The executor's entry (`executor._CompiledEntry`) of `program`
    jitted over `mesh`, for both runners: a feed as `feed_sharding(name)`
    says (a ragged one comes replicated), a state name as
    `state_sharding(name)`. The state goes in flat, in the
    SORTED order in which jit flattens a dict (lowering.StateCallable:
    the compiled program's parameter list is what it was when the state
    went in by name), so a run hands on the tuples the run before it
    left; its `flat` is jitted anew, with the mesh's shardings, the
    read-written leaves donated. What a sharded entry is told: its call
    runs in the mesh, under `api._ACTIVE_MESH` / `_ACTIVE_PARAM_SPEC`
    (sharding_constraint ops resolve specs, fused units partition state
    by its actual placement, while it traces); in one process a leaf the
    walk found elsewhere is
    moved where the entry wants it, once (`place_state`), and the step
    keeps its record like any other; across processes the leaves and the
    key become global arrays, no record is kept, and nothing is retried
    (one process re-entering a collective alone hangs the others)."""
    from . import api
    from ..executor import Executor, _CompiledEntry, _keep_nothing, _raise
    read, written = lowering.analyze_state(program, fetch_names)
    needed = Executor._read_before_write(program, read, written,
                                         set(feed_names), fetch_names)
    lod_out = {}
    fn, ro_names, rw_names = lowering.build_fn(
        program, fetch_names, needed, written, static_lods=static_lods,
        lod_out=lod_out, lower_params=lower_params)
    feed_shardings = {k: feed_sharding(k) for k in feed_names}
    state_shardings = {n: state_sharding(n)
                       for n in set(ro_names) | set(rw_names) | set(written)}

    def param_spec(name):
        return (state_shardings.get(name) or state_sharding(name)).spec
    repl = NamedSharding(mesh, P())
    call = lowering.StateCallable(fn, ro_names, rw_names, program, True)
    call.flat = flat = jax.jit(
        call._fn,
        in_shardings=(feed_shardings,
                      tuple([state_shardings[n] for n in call.ro_names]),
                      tuple([state_shardings[n] for n in call.rw_names]),
                      repl),
        out_shardings=(None, {n: state_shardings[n] for n in written}),
        donate_argnums=(2,))
    alone = jax.process_count() == 1

    def in_mesh(feed, ro, rw, key):
        prev = api._ACTIVE_MESH, api._ACTIVE_PARAM_SPEC
        api._ACTIVE_MESH, api._ACTIVE_PARAM_SPEC = mesh, param_spec
        try:
            with mesh:
                return flat(feed, ro, rw,
                            key if alone else _global(repl, key))
        finally:
            api._ACTIVE_MESH, api._ACTIVE_PARAM_SPEC = prev

    def place(scope, names, leaves, program):
        if alone:
            return tuple(place_state(scope, dict(zip(names, leaves)),
                                     state_shardings, program).values())
        return tuple([_global(state_shardings[n], v)
                      for n, v in zip(names, leaves)])
    how = {} if alone else {'keep': _keep_nothing, 'retry': _raise}
    return _CompiledEntry(call, fetch_names, written, program, lod_out,
                          kind='mesh', call=in_mesh, place=place,
                          to_host=fetch_to_host,
                          feed_shardings=feed_shardings,
                          state_shardings=state_shardings, **how)


class DataParallelRunner(object):
    def __init__(self, program, loss_name=None, build_strategy=None,
                 places=None, mesh=None):
        self._program = program
        self._loss_name = loss_name
        self._build_strategy = build_strategy
        self._mesh = mesh if mesh is not None else data_mesh(
            len(places) if places else None)
        self._cache = {}

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
        program, mesh = self._program, self._mesh
        lower_params, reduce_mode = self._strategy_knobs()
        bs = self._build_strategy
        if bs is not None and getattr(bs, 'debug_graphviz_path', ''):
            from ..debugger import draw_block_graphviz
            draw_block_graphviz(program, bs.debug_graphviz_path)
        feed_lods = dict(feed_lods or {})
        repl = NamedSharding(mesh, P())
        batch_sharded = NamedSharding(mesh, P('data'))
        # ragged (LoD) feeds replicate: rows are per-sequence, not evenly
        # splittable over devices (reference SplitLoDTensor splits by
        # instance at feed time; the TPU path is bucket+pad to dense —
        # reader/bucketing.py — when scaling matters)
        return sharded_entry(
            program, mesh, feed, fetch_names, feed_lods,
            lambda k: repl if k in feed_lods else batch_sharded,
            lambda n: self._state_sharding(program, n, reduce_mode, mesh),
            lower_params)

    def run(self, executor, feed, fetch_list, scope, return_numpy):
        """One step, the executor's (`Executor._step`: its phases, its
        take / call / commit, `executor_run_phase_seconds_total{phase}`),
        on the entry compiled for the mesh. Runs are counted where the
        CompiledProgram delegates (executor_run_total, compiler.py)."""
        from ..executor import global_scope
        if scope is None:
            scope = global_scope()
        program = self._program

        def find():
            feed2, fetch_names, _, static_lods = \
                executor._prepare_run_inputs(program, feed, scope,
                                             fetch_list)
            nproc = jax.process_count()
            # under multi-host, each process feeds its LOCAL batch shard
            # (reference: each trainer reads its own data slice);
            # divisibility is per local device count
            ndev = self.num_devices // nproc
            for k, v in feed2.items():
                if k in static_lods:
                    continue      # ragged feeds replicate (see _compile)
                if v.shape and v.shape[0] % max(ndev, 1) != 0:
                    raise ValueError(
                        "feed %r batch %d not divisible by %d mesh devices"
                        % (k, v.shape[0], ndev))
            # the sharded jit donates, whatever the policy: no override
            # applies and no rate moves
            key = executor._entry_key(program, feed2, static_lods, (),
                                      fetch_names, True, ('mesh',), False)
            entry, since = executor._find(
                key, program,
                lambda: self._compile(feed2, fetch_names, static_lods),
                self._cache)
            if nproc > 1:
                feed2 = global_feed(entry.feed_shardings, feed2)
            return entry, since, feed2
        entry, fetches, _ = executor._step(scope, program, find)
        return executor._fetch(entry, fetches, return_numpy)
