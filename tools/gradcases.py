"""Grad-ify the optest corpus: chip-side gradient validation cases.

The reference validates analytic gradients per op on EVERY place
(python/paddle/fluid/tests/unittests/op_test.py:418,433 check_grad /
check_grad_with_place, reused by the mkldnn/ngraph second-place suites).
The collected TPU replay corpus (optest_cases/case_*.pkl) is forward-only
in practice, so this tool derives the second-place grad programs from it:

  for each forward case, clone its program, append the `backward` meta op
  (core/lowering.py lowers it via jax.vjp) targeting the first float fetch
  with every float feed/state leaf as wrt, run it on CPU to record the
  analytic gradients as fetches, and save a gradcase_*.pkl that
  tools/tpu_optest.py replays on the real TPU exactly like a forward case.

Grad coverage accounting is path-based: an op type counts as grad-covered
only if it sits on a wrt->target dependency path (its vjp actually runs),
not merely somewhere in the program.

Run on CPU:  JAX_PLATFORMS=cpu python tools/gradcases.py [corpus_dir]
"""
import glob
import os
import pickle
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

# ops whose presence makes a case non-grad-ifiable: not reverse-mode
# differentiable (while lowers to lax.while_loop), stateful/host-side, or
# meaningless to differentiate (optimizers mutate state in-place)
SKIP_OPS = {
    'while', 'backward', 'py_func', 'print', 'save', 'load',
    'save_combine', 'load_combine', 'feed', 'fetch', 'read',
    'create_py_reader', 'read_from_array', 'write_to_array',
    'increment', 'less_than', 'gpipe_run', 'switch_moe',
}
_FLOATS = (np.float16, np.float32, np.float64)

# Source cases whose gradients are DISCONTINUOUS at the recorded inputs,
# so a CPU/TPU comparison measures tie-breaking, not op semantics:
#  - case_0007: sequence_pool(MAX) over saturated LSTM outputs — dozens of
#    rows are bitwise-tied at tanh's f32 saturation value, and a ~1e-5
#    forward delta reroutes the entire max cotangent to different rows
#    (bisected on-chip: grads match to 1e-7 up through the lstm op, then
#    jump to O(1) across the pool). The ops it would cover (lookup_table,
#    softmax, lstm, sequence_pool grads) are covered by other cases with
#    untied inputs.
_UNSTABLE_SOURCES = {'case_0007_14821.pkl'}


def _is_float(arr):
    return np.issubdtype(np.asarray(arr).dtype, np.floating)


def _grad_path_ops(ops, wrt_names, target):
    """Op types on a wrt->target dependency path (main-block ops, in
    program order; `backward` and later ops excluded by the caller)."""
    reach = set(wrt_names)
    fwd_hit = []
    for op in ops:
        if set(op.input_arg_names) & reach:
            reach.update(op.output_arg_names)
            fwd_hit.append(op)
    anc = {target}
    path = set()
    for op in reversed(fwd_hit):
        if set(op.output_arg_names) & anc:
            anc.update(op.input_arg_names)
            path.add(op.type)
    return path


def _build_and_run(case):
    """Lower the (grad-ified) program and execute on the current backend;
    mirrors tools/tpu_optest.py _build."""
    import jax
    from paddle_tpu.core import lowering
    from paddle_tpu.executor import Executor
    program = case['program']
    fetch_names = case['fetch_names']
    feed_arrays = {k: (v[0] if isinstance(v, tuple) else v)
                   for k, v in case['feed'].items()}
    read, written = lowering.analyze_state(program, fetch_names)
    needed = Executor._read_before_write(program, read, written,
                                         set(feed_arrays), fetch_names)
    static_names = Executor._static_feed_names(program)
    static_feed = {n: np.asarray(feed_arrays[n]) for n in static_names
                   if n in feed_arrays}
    fn, ro_names, rw_names = lowering.build_fn(
        program, fetch_names, needed, written,
        static_lods=case['static_lods'], static_feed=static_feed)
    ro = {n: case['ro'][n] for n in ro_names}
    rw = {n: case['rw'][n] for n in rw_names}
    fetches, _ = jax.jit(fn)(feed_arrays, ro, rw, case['key'])
    return [np.asarray(f) for f in fetches]


def gradify(name, case, seen_tokens):
    """Return (gradcase dict, new tokens) or (None, reason)."""
    from paddle_tpu.framework import grad_var_name
    from paddle_tpu.core.selected_rows import SelectedRows

    ops = case['ops']
    if name in _UNSTABLE_SOURCES:
        return None, 'unstable-grad-source'
    if SKIP_OPS & set(ops):
        return None, 'skip-op'
    program = case['program'].clone()
    block = program.global_block()
    main_ops = list(block.ops)

    # targets: every fetched float var (cap 4). The grad target is the
    # combined scalar sum_k mean(square(fetch_k)) — squaring breaks the
    # softmax-family degeneracy where rows sum to a constant and the mean's
    # gradient collapses to ~0, which would validate nothing.
    targets = [fname for fname, val
               in zip(case['fetch_names'], case['cpu_fetches'])
               if _is_float(val) and np.asarray(val).size
               and block.has_var(fname)][:4]
    if not targets:
        return None, 'no-float-fetch'
    means = []
    for k, fname in enumerate(targets):
        sq = block.create_var(name='__gradloss_sq%d' % k,
                              stop_gradient=False)
        block.append_op(type='square', inputs={'X': [block.var(fname)]},
                        outputs={'Out': [sq]})
        mn = block.create_var(name='__gradloss_mean%d' % k,
                              stop_gradient=False)
        block.append_op(type='mean', inputs={'X': [sq]},
                        outputs={'Out': [mn]})
        means.append(mn)
    if len(means) == 1:
        loss_var = means[0]
    else:
        loss_var = block.create_var(name='__gradloss', stop_gradient=False)
        block.append_op(type='sum', inputs={'X': means},
                        outputs={'Out': [loss_var]})
    target = loss_var.name
    main_ops = list(block.ops)

    # wrt leaves: float feeds + float state actually read by the program
    read_names = set()
    for b in program.blocks:
        for op in b.ops:
            read_names.update(op.input_arg_names)
    wrt = []
    for src in ('feed', 'ro', 'rw'):
        for k, v in case[src].items():
            arr = v[0] if isinstance(v, tuple) else v
            if k in read_names and _is_float(arr) and k != target \
                    and block.has_var(k) and k not in wrt:
                wrt.append(k)
    wrt = wrt[:16]
    if not wrt:
        return None, 'no-float-leaf'

    tokens = {'grad:' + t for t in _grad_path_ops(main_ops, wrt, target)
              if t != 'fetch'}
    new = tokens - seen_tokens
    if not new:
        return None, 'no-new-coverage'
    # only differentiate wrt leaves that actually reach the target
    live = _live_wrt(main_ops, wrt, target)
    if not live:
        return None, 'no-live-leaf'
    wrt = [n for n in wrt if n in live]

    grad_vars = []
    for n in wrt:
        v = block.var(n)
        grad_vars.append(block.create_var(
            name=grad_var_name(n), shape=v.shape, dtype=v.dtype,
            persistable=False, stop_gradient=False))
    block.append_op(type='backward',
                    inputs={'Loss': [block.var(target)]},
                    outputs={'Grads': grad_vars},
                    attrs={'wrt_names': list(wrt)})

    gcase = dict(case)
    gcase['program'] = program
    gcase['ops'] = [op.type for b in program.blocks for op in b.ops]
    gcase['fetch_names'] = [g.name for g in grad_vars]
    gcase['grad_ops'] = sorted(t[5:] for t in tokens)
    gcase['new_ops'] = sorted(new)
    gcase['source_case'] = name
    try:
        fetches = _build_and_run(gcase)
    except Exception as e:
        return None, 'build/run: %s: %s' % (type(e).__name__, str(e)[:160])
    for f in fetches:
        if isinstance(f, SelectedRows):
            return None, 'selected-rows-grad'
        if _is_float(f) and not np.isfinite(f).all():
            return None, 'non-finite-grad'
    # an all-zero gradient set validates nothing
    if not any(_is_float(f) and f.size and np.abs(f).max() > 0
               for f in fetches):
        return None, 'all-zero-grads'
    gcase['cpu_fetches'] = fetches
    return gcase, new


def _synthetic_cases():
    """Hand-built forward cases for diffable ops the collected corpus only
    exercises on non-differentiable paths (cast appears only as f32->int;
    top_k only under beam search / accuracy int paths)."""
    from paddle_tpu.framework import Program
    from paddle_tpu.executor import _run_key

    rng = np.random.RandomState(7)
    probs = np.abs(rng.randn(4, 5).astype('float32')) + 0.1
    probs /= probs.sum(1, keepdims=True)
    specs = [
        ('cast', {'X': rng.randn(4, 6).astype('float32')},
         {'in_dtype': 'float32', 'out_dtype': 'float16'},
         {'X': ['X']}, {'Out': ['Out']}),
        ('top_k', {'X': rng.randn(4, 10).astype('float32')},
         {'k': 3},
         {'X': ['X']}, {'Out': ['Out'], 'Indices': ['Indices']}),
        ('assign', {'X': rng.randn(3, 4).astype('float32')}, {},
         {'X': ['X']}, {'Out': ['Out']}),
        ('cross_entropy',
         {'X': probs, 'Label': np.array([[0], [2], [1], [4]], 'int64')},
         {}, {'X': ['X'], 'Label': ['Label']}, {'Y': ['Y']}),
    ]
    out = []
    for op_type, feeds, attrs, in_map, out_map in specs:
        prog = Program()
        block = prog.global_block()
        ins = {}
        for slot, names in in_map.items():
            ins[slot] = [block.create_var(
                name=n, shape=feeds[n].shape, dtype=feeds[n].dtype,
                stop_gradient=False) for n in names]
        outs = {}
        for slot, names in out_map.items():
            outs[slot] = [block.create_var(name=n, stop_gradient=False)
                          for n in names]
        block.append_op(type=op_type, inputs=ins, outputs=outs,
                        attrs=attrs)
        fetch_names = [v.name for vs in outs.values() for v in vs]
        case = {
            'ops': [op.type for b in prog.blocks for op in b.ops],
            'new_ops': [op_type], 'program': prog, 'feed': feeds,
            'static_lods': {}, 'ro': {}, 'rw': {},
            'key': np.asarray(_run_key(0, 0, 1)),
            'fetch_names': fetch_names,
        }
        try:
            case['cpu_fetches'] = _build_and_run(case)
        except Exception as e:
            print("  synthetic %s forward failed: %s: %s"
                  % (op_type, type(e).__name__, str(e)[:160]))
            continue
        out.append(('synthetic_%s' % op_type, case))
    return out


def _live_wrt(ops, wrt, target):
    """Wrt leaves with a dependency path to target."""
    live = set()
    for n in wrt:
        reach = {n}
        for op in ops:
            if set(op.input_arg_names) & reach:
                reach.update(op.output_arg_names)
        if target in reach:
            live.add(n)
    return live


def main():
    d = sys.argv[1] if len(sys.argv) > 1 else 'optest_cases'
    import jax
    jax.config.update('jax_platforms', 'cpu')
    if jax.devices()[0].platform != 'cpu':
        print("gradcases must run on CPU (JAX_PLATFORMS=cpu) — the CPU run "
              "is the reference side of the second-place comparison")
        sys.exit(2)
    jax.config.update('jax_default_matmul_precision', 'highest')

    for old in glob.glob(os.path.join(d, 'gradcase_*.pkl')):
        os.remove(old)
    cases = []
    for p in sorted(glob.glob(os.path.join(d, 'case_*.pkl'))):
        with open(p, 'rb') as f:
            cases.append((os.path.basename(p), pickle.load(f)))
    cases.extend(_synthetic_cases())
    # smallest programs first: they isolate single ops, so each op's grad
    # coverage lands on the most debuggable case
    cases.sort(key=lambda nc: (len(nc[1]['ops']), nc[0]))

    seen = set()
    kept = 0
    reasons = {}
    for name, case in cases:
        gcase, res = gradify(name, case, seen)
        if gcase is None:
            reasons[res] = reasons.get(res, 0) + 1
            if res.startswith('build/run'):
                print("  %s: %s" % (name, res))
            continue
        seen.update(res)
        kept += 1
        out = os.path.join(d, 'gradcase_%04d.pkl' % kept)
        with open(out, 'wb') as f:
            pickle.dump(gcase, f, protocol=4)
    print("%d gradcases; %d grad-covered op types" % (kept, len(seen)))
    for r, n in sorted(reasons.items()):
        print("  skipped %-24s %d" % (r, n))
    toks = sorted(t[5:] for t in seen)
    print("grad-covered:", ' '.join(toks))


if __name__ == '__main__':
    main()
