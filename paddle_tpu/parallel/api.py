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

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import monitor

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


class MeshRunner(object):
    def __init__(self, program, mesh, param_rules=None, feed_specs=None,
                 fetch_specs=None):
        from ..executor import Executor
        self._program = program
        self._mesh = mesh
        self._rules = param_rules if isinstance(param_rules, ShardingRules) \
            else ShardingRules(param_rules)
        self._feed_specs = dict(feed_specs or {})
        self._cache = {}
        # the step is the executor's; this one runs nothing else, so its
        # run counter is the runner's
        self._exe = Executor()

    def _sharding(self, spec):
        return NamedSharding(self._mesh, spec)

    def compile(self, feed_shapes, fetch_names, scope, feed_lods=None):
        """feed_shapes: {name: (shape, dtype)}. The executor's entry
        (`spmd.sharded_entry`) of the program under the rules — resolved
        once per compile (regexes are not something to re-match every
        step). Ragged (LoD) feeds are replicated: their row counts are
        per-sequence, not per-device-splittable; bucket+pad to dense
        (reader/bucketing.py, layers.sequence_pad) to shard them."""
        from .spmd import sharded_entry
        feed_lods = dict(feed_lods or {})
        return sharded_entry(
            self._program, self._mesh, feed_shapes, fetch_names, feed_lods,
            lambda k: self._sharding(P() if k in feed_lods
                                     else self._feed_specs.get(k, P())),
            lambda n: self._sharding(self._rules.spec_for(n)))

    def run(self, feed, fetch_list, scope, return_numpy=True):
        """One step, the executor's (`Executor._step`: its phases —
        executor_run_phase_seconds_total{phase=prepare|dispatch|commit|
        fetch}; a signature's first call is set-up's frame and the
        `compile` phase —, its take / call / commit), counted in
        executor_run_total."""
        from ..executor import global_scope
        from .spmd import global_feed
        if scope is None:
            scope = global_scope()
        monitor.inc('executor_run_total')
        program, exe = self._program, self._exe

        def find():
            feed2, fetch_names, _, static_lods = exe._prepare_run_inputs(
                program, feed, scope, fetch_list)
            # the sharded jit donates, whatever the policy
            key = exe._entry_key(program, feed2, static_lods, (),
                                 fetch_names, True, ('mesh',), False)
            entry, since = exe._find(
                key, program, lambda: self.compile(
                    {k: (v.shape, v.dtype) for k, v in feed2.items()},
                    fetch_names, scope, static_lods), self._cache)
            if jax.process_count() > 1:
                # multi-host: feeds are per-process local shards, state
                # is replicated-identical — assemble global arrays (the
                # same contract as spmd.DataParallelRunner; reference:
                # each trainer feeds its own slice, params broadcast once)
                feed2 = global_feed(entry.feed_shardings, feed2)
            return entry, since, feed2
        entry, fetches, _ = exe._step(scope, program, find)
        return exe._fetch(entry, fetches, return_numpy)
