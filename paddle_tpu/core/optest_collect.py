"""Second-place case collection (reference tests/unittests/op_test.py:304
check_output_with_place + the mkldnn-suite pattern of re-running the same
tests on another place).

With PADDLE_OPTEST_COLLECT_DIR set, every Executor.run records the executed
(program, feed, static LoDs, state, PRNG key, fetch names, CPU fetch
values) as a pickled case file — but only when the case ADDS op-type
coverage, so one full CPU test-suite run distills to a few hundred compact
cases covering the registered op surface. tools/tpu_optest.py replays them
on the real TPU, batching many programs per compiled call to amortize
launch and compile latency, and reports per-op tolerance deltas.
"""
import os
import pickle

import numpy as np

_seen_ops = set()
_case_counter = [0]
_MAX_CASE_BYTES = 64 << 20
_MAX_OPS = 400


def _nbytes(tree):
    total = 0
    for v in tree.values() if isinstance(tree, dict) else tree:
        if isinstance(v, tuple):
            v = v[0]
        arr = np.asarray(v)
        total += arr.nbytes
    return total


def record_case(program, feed, static_lods, ro_state, rw_state, key_arr,
                fetch_names, fetches):
    out_dir = os.environ.get('PADDLE_OPTEST_COLLECT_DIR')
    if not out_dir:
        return
    try:
        ops = [op.type for block in program.blocks for op in block.ops]
        new = set(ops) - _seen_ops
        if not new or not fetch_names or len(ops) > _MAX_OPS:
            return
        case = {
            'ops': ops,
            'new_ops': sorted(new),
            'feed': {k: ((np.asarray(v[0]), v[1])
                         if isinstance(v, tuple) else np.asarray(v))
                     for k, v in feed.items()},
            'static_lods': dict(static_lods or {}),
            'ro': {k: np.asarray(v) for k, v in ro_state.items()},
            'rw': {k: np.asarray(v) for k, v in rw_state.items()},
            'key': np.asarray(key_arr),
            'fetch_names': list(fetch_names),
            'cpu_fetches': [np.asarray(f) for f in fetches],
        }
        if (_nbytes(case['feed']) + _nbytes(case['ro'])
                + _nbytes(case['rw'])) > _MAX_CASE_BYTES:
            return
        if not all(np.isfinite(f).all() for f in case['cpu_fetches']
                   if np.issubdtype(f.dtype, np.floating)):
            return
        case['program'] = program.clone()
        os.makedirs(out_dir, exist_ok=True)
        _case_counter[0] += 1
        path = os.path.join(out_dir, 'case_%04d_%d.pkl'
                            % (_case_counter[0], os.getpid()))
        with open(path, 'wb') as f:
            pickle.dump(case, f, protocol=4)
        # only after a successful dump: a failed pickle must not burn
        # these op types' one shot at collection
        _seen_ops.update(new)
    except Exception:
        # collection must NEVER break the suite run it shadows
        pass
