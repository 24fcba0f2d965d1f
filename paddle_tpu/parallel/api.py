"""MeshRunner: run a Program SPMD over an arbitrary mesh with sharding rules.

This is the TPU-native replacement for the reference DistributeTranspiler
(python/paddle/fluid/transpiler/distribute_transpiler.py:161): instead of
rewriting the program with send/recv/pserver ops, you declare
- a mesh (axes like data/model/seq/expert),
- regex rules mapping parameter names -> PartitionSpec (tensor parallel /
  sharded "parameter server" placement),
- feed specs mapping feed names -> PartitionSpec (data/sequence parallel),
and the SAME program compiles to one SPMD executable; the XLA partitioner
inserts all collectives (psum/all_gather/reduce_scatter/all_to_all) over ICI.

`sharding_constraint` ops inside the program (layers.nn.sharding_constraint)
pin intermediate activations to specs — the mechanism for sequence
parallelism and megatron-style activation sharding.
"""
import re
import time

import numpy as np
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import coldstart
from .. import monitor
from ..core import lowering
from ..framework import Variable

__all__ = ['ShardingRules', 'MeshRunner', 'get_active_mesh',
           'get_active_param_spec']

# Mesh visible to op lowerings while a MeshRunner traces its program
# (sharding_constraint ops resolve PartitionSpecs against it).
_ACTIVE_MESH = None

# name -> PartitionSpec resolver for the runner that activated the mesh
# (MeshRunner: its ShardingRules; DataParallelRunner: replicated, or the
# ZeRO-style reduce-mode placement). Mesh-native fused units consult it so
# e.g. fused_adam partitions each parameter by its OWN spec instead of
# all-gathering a sharded parameter set (ops/optimizer_ops.py).
_ACTIVE_PARAM_SPEC = None


def get_active_mesh():
    return _ACTIVE_MESH


def get_active_param_spec():
    """The active runner's name->PartitionSpec resolver, or None outside a
    runner trace (callers treat None as all-replicated)."""
    return _ACTIVE_PARAM_SPEC


class ShardingRules(object):
    """Ordered (regex, PartitionSpec) list; first match wins; default
    replicated."""

    def __init__(self, rules=None):
        self._rules = [(re.compile(pat), spec) for pat, spec in
                       (rules or [])]

    def add(self, pattern, spec):
        self._rules.append((re.compile(pattern), spec))
        return self

    def spec_for(self, name):
        for pat, spec in self._rules:
            if pat.search(name):
                return spec
        return P()


class _MeshEntry(object):
    __slots__ = ('fn', 'ro_names', 'rw_names', 'lod_out', 'state_shardings')

    def __init__(self, fn, ro_names, rw_names, lod_out, state_shardings):
        self.fn = fn
        self.ro_names = ro_names
        self.rw_names = rw_names
        self.lod_out = lod_out if lod_out is not None else {}
        # {state name: NamedSharding}, resolved once per compile (the
        # rules are regexes — not something to re-match every step)
        self.state_shardings = state_shardings


class MeshRunner(object):
    def __init__(self, program, mesh, param_rules=None, feed_specs=None,
                 fetch_specs=None):
        self._program = program
        self._mesh = mesh
        self._rules = param_rules if isinstance(param_rules, ShardingRules) \
            else ShardingRules(param_rules)
        self._feed_specs = dict(feed_specs or {})
        self._cache = {}
        self._run_counter = 0

    def _sharding(self, spec):
        return NamedSharding(self._mesh, spec)

    def compile(self, feed_shapes, fetch_names, scope, feed_lods=None):
        """feed_shapes: {name: (shape, dtype)}."""
        program = self._program
        read, written = lowering.analyze_state(program, fetch_names)
        from ..executor import Executor
        needed = Executor._read_before_write(
            program, read, written, set(feed_shapes), fetch_names)
        feed_lods = dict(feed_lods or {})
        lod_out = {}
        fn, ro_names, rw_names = lowering.build_fn(
            program, fetch_names, needed, written,
            static_lods=feed_lods, lod_out=lod_out)
        in_shardings = (
            # ragged (LoD) feeds are replicated: their row counts are
            # per-sequence, not per-device-splittable; bucket+pad to dense
            # (reader/bucketing.py, layers.sequence_pad) to shard them
            {k: self._sharding(P() if k in feed_lods
                               else self._feed_specs.get(k, P()))
             for k in feed_shapes},
            {n: self._sharding(self._rules.spec_for(n)) for n in ro_names},
            {n: self._sharding(self._rules.spec_for(n)) for n in rw_names},
            self._sharding(P()),
        )
        out_shardings = (
            None,
            {n: self._sharding(self._rules.spec_for(n)) for n in written},
        )
        jitted = jax.jit(fn, in_shardings=in_shardings,
                         out_shardings=out_shardings, donate_argnums=(2,))
        return jitted, ro_names, rw_names, lod_out

    def run(self, feed, fetch_list, scope, return_numpy=True):
        """One step, in Executor.run's phases
        (executor_run_phase_seconds_total{phase=prepare|dispatch|commit|
        fetch}; a signature's first call is set-up's frame and the
        `compile` phase), counted in executor_run_total."""
        from ..executor import (global_scope, _run_phase, _compile_frame,
                                _fetched, _goodput_leaf)
        from .. import analysis
        from .. import goodput
        if scope is None:
            scope = global_scope()
        monitor.inc('executor_run_total')
        with _run_phase('prepare'):
            entry, feed, ro, rw, key_arr, fetch_names, since = \
                self._prepare(feed, fetch_list, scope)
        program, fn = self._program, entry.fn
        global _ACTIVE_MESH, _ACTIVE_PARAM_SPEC
        prev, _ACTIVE_MESH = _ACTIVE_MESH, self._mesh
        prev_spec, _ACTIVE_PARAM_SPEC = (_ACTIVE_PARAM_SPEC,
                                         self._rules.spec_for)
        try:
            with self._mesh:
                if since is not None:
                    # the jit compile lands inside this first call: its
                    # wall is compile cost (the goodput 'compile' loss
                    # bucket)
                    with _compile_frame(program, since=since) as frame:
                        fetches, new_state = fn(feed, ro, rw, key_arr)
                else:
                    with _run_phase('dispatch'):
                        t_disp = time.perf_counter()
                        fetches, new_state = fn(feed, ro, rw, key_arr)
                        t_staged = time.perf_counter()
        finally:
            _ACTIVE_MESH = prev
            _ACTIVE_PARAM_SPEC = prev_spec
        with _run_phase('commit'):
            fp = program._fingerprint()
            if since is not None:
                # the executable registers for XLA flops/bytes analytics
                # so mesh dispatches carry MFU like every other kind
                goodput.note_compile(fp, frame.seconds)
                analysis.record_compiled(fn, program,
                                         (feed, ro, rw, key_arr),
                                         kind='mesh')
            else:
                goodput.note_dispatch(fp, 'mesh', t_disp, t_staged,
                                      leaf=_goodput_leaf(new_state,
                                                         list(fetches)))
            scope.update(new_state)
            # propagate produced LoDs of written persistables into the
            # scope
            for n in new_state:
                lod = entry.lod_out.get(n)
                if lod:
                    scope._lods[n] = lod
                else:
                    scope._lods.pop(n, None)
        if not return_numpy:
            return list(fetches)
        from .spmd import DataParallelRunner
        host = DataParallelRunner._fetch_to_host
        with _run_phase('fetch'):
            return [
                _fetched(host(f), entry.lod_out[n])
                if entry.lod_out.get(n) else host(f)
                for n, f in zip(fetch_names, fetches)]

    def _prepare(self, feed, fetch_list, scope):
        """Everything of a run ahead of the sharded call: (entry, feed,
        ro, rw, key, fetch names, and — for a signature's first run,
        whose entry was made here — when its making began)."""
        from ..executor import Executor
        program = self._program
        exe = Executor()
        feed, feed_lods = exe._prepare_feed(program, feed or {})
        fetch_names = [v.name if isinstance(v, Variable) else v
                       for v in (fetch_list or [])]
        # LoD-carrying scope state binds statically, like the serial
        # executor (executor.py scope_lods handling)
        from ..core.lod import normalize_lod as _nl
        scope_lods = {n: _nl(l) for n, l in
                      getattr(scope, '_lods', {}).items() if l}
        static_lods = dict(scope_lods)
        static_lods.update(feed_lods)
        key = (program._version, exe._feed_signature(feed, static_lods),
               tuple(fetch_names))
        entry = self._cache.get(key)
        since = None
        if entry is None:
            since = time.perf_counter()
            from ..executor import _wire_persistent_cache, _RUN_COMPILE
            _wire_persistent_cache()
            with coldstart.stage('trace', program, *_RUN_COMPILE):
                fn_, ro_, rw_, lod_out_ = self.compile(
                    {k: (v.shape, v.dtype) for k, v in feed.items()},
                    fetch_names, scope, feed_lods=static_lods)
            entry = _MeshEntry(
                fn_, ro_, rw_, lod_out_,
                {n: self._sharding(self._rules.spec_for(n))
                 for n in list(ro_) + list(rw_)})
            self._cache[key] = entry
        ro_names, rw_names = entry.ro_names, entry.rw_names
        ro = {n: exe._state_value(scope, n, program) for n in ro_names}
        rw = {n: exe._state_value(scope, n, program) for n in rw_names}
        if jax.process_count() == 1:
            from .spmd import place_state
            ro = place_state(scope, ro, entry.state_shardings, program)
            rw = place_state(scope, rw, entry.state_shardings, program)
        self._run_counter += 1
        from ..executor import _run_key, _next_program_run
        key_arr = _run_key(program.random_seed, _next_program_run(program),
                           self._run_counter)
        if jax.process_count() > 1:
            # multi-host: feeds are per-process local shards, state is
            # replicated-identical — assemble global arrays (the same
            # contract as spmd.DataParallelRunner; reference: each trainer
            # feeds its own slice, params broadcast once)
            def _glob_feed(name, v):
                if isinstance(v, jax.Array) and not v.is_fully_addressable:
                    return v
                sh = self._sharding(P() if name in static_lods
                                    else self._feed_specs.get(name, P()))
                return jax.make_array_from_process_local_data(
                    sh, np.asarray(v))

            def _glob_state(name, v):
                if isinstance(v, jax.Array) and not v.is_fully_addressable:
                    return v
                arr = np.asarray(v)
                sh = self._sharding(self._rules.spec_for(name))
                return jax.make_array_from_callback(
                    arr.shape, sh, lambda idx: arr[idx])

            feed = {k: _glob_feed(k, v) for k, v in feed.items()}
            ro = {n: _glob_state(n, v) for n, v in ro.items()}
            rw = {n: _glob_state(n, v) for n, v in rw.items()}
            karr = np.asarray(key_arr)
            key_arr = jax.make_array_from_callback(
                karr.shape, self._sharding(P()), lambda idx: karr[idx])
        return entry, feed, ro, rw, key_arr, fetch_names, since
