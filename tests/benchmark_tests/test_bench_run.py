"""run.py end to end at toy width on the CPU (the platform check is
overridden HERE, in the test: run.py has no CPU mode), the no-TPU refusal,
and the manifest: every cell resolves to files that exist and every name
and unit is made of the allowed characters."""
import importlib.util
import json
import os
import re

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TOY_MANIFEST = os.path.join(HERE, 'fixtures', 'BENCHMARK.toy.json')

NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')
PATH = re.compile(r'^[A-Za-z0-9_.\-/]{1,200}$')
SOURCES = {'device_trace', 'program_span', 'program_counter', 'host_clock'}


def _load_run():
    spec = importlib.util.spec_from_file_location(
        'bench_run_under_test', os.path.join(ROOT, 'benchmark', 'run.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def trace_into(monkeypatch, run, tmp_path):
    """A traced run of this test writes its trace under the test's own
    directory: several test files trace the same toy cell, the driver runs
    the files in parallel, and run.py's `.bench_trace/<workload>` would be
    one directory for all of them, removed by whichever ends first."""
    init = run.Context.__init__

    def __init__(self, args, *rest):
        init(self, args, *rest)
        self.trace_dir = str(tmp_path / 'bench_trace' / args.workload)
    monkeypatch.setattr(run.Context, '__init__', __init__)


@pytest.fixture()
def run_on_cpu(monkeypatch, tmp_path):
    """run.py with its platform check and its choice of trace line
    overridden for the CPU backend — in the test, not in the program."""
    import jax
    from benchmark import flops, reduce_trace
    run = _load_run()
    trace_into(monkeypatch, run, tmp_path)
    monkeypatch.setattr(
        run, 'require_devices',
        lambda chips: (jax.devices(), flops.peaks_for('TPU v5 lite')))
    monkeypatch.setattr(
        reduce_trace, 'is_ops_line',
        lambda plane, line: plane == '/host:CPU'
        and line.startswith('tf_XLAPjRtCpuClient'))
    return run


def _last_json(capsys):
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    return json.loads(lines[-1]), lines


CONTRACT_KEYS = {'correct', 'attempted', 'failed', 'metrics', 'device'}
DEVICE_KEYS = {'platform', 'kind', 'count', 'memory_peak_bytes'}


@pytest.mark.parametrize('workload,metrics', [
    ('toy-train', {'train_tokens_per_s', 'setup_s'}),
    ('toy-serve', {'serve_tokens_per_s', 'ttft_p95_ms', 'itl_p95_ms',
                   'setup_s'})])
def test_end_to_end_line(run_on_cpu, capsys, workload, metrics):
    rc = run_on_cpu.main(['--workload', workload, '--seed', '3000000001',
                          '--seconds', '0.5', '--trace', '0'],
                         manifest_path=TOY_MANIFEST)
    out, _lines = _last_json(capsys)
    assert rc == 0 and set(out) == CONTRACT_KEYS
    assert out['correct'] is True and out['failed'] == 0
    assert out['attempted'] > 0
    assert set(out['metrics']) == metrics
    assert all(set(v) == {'value', 'unit'} and v['value'] > 0
               for v in out['metrics'].values())
    assert set(out['device']) == DEVICE_KEYS


@pytest.mark.parametrize('workload,metrics', [
    ('toy-train', {'train_step_gap_ms', 'train_step_mfu',
                   'pallas_time_share', 'device_idle_share.train'}),
    ('toy-serve', {'decode_step_ms', 'prefill_ms.ttft', 'decode_hbm_share',
                   'device_idle_share.serve'})])
def test_traced_line(run_on_cpu, capsys, tmp_path, workload, metrics):
    rc = run_on_cpu.main(['--workload', workload, '--seed', '7',
                          '--seconds', '0.7', '--trace', '1'],
                         manifest_path=TOY_MANIFEST)
    out, _lines = _last_json(capsys)
    assert rc == 0 and set(out) == CONTRACT_KEYS | {'breakdown'}
    assert out['correct'] is True
    # peak_hbm_gb.* has nothing to read on the CPU: its reader returns
    # nothing and the line leaves it out
    assert set(out['metrics']) == metrics
    assert set(out['device']) == DEVICE_KEYS | {'busy_s', 'window_s'}
    assert 0 < out['device']['busy_s'] <= out['device']['window_s']
    assert set(out['breakdown']) == {'device_ops', 'idle_gaps'}
    assert 0 < len(out['breakdown']['device_ops']) <= 10
    assert len(out['breakdown']['idle_gaps']) <= 10
    assert not (tmp_path / 'bench_trace' / workload).exists()   # removed


# ---- train_mesh: four forced host devices -----------------------------

def _losses(lines):
    """(first train step loss, first and last loss of the window), as the
    drivers' notes print them (four decimals)."""
    first = [ln for ln in lines if 'first train step' in ln][0]
    window = [ln for ln in lines if '] window: ' in ln][0]
    a, b = re.search(r'loss ([0-9.]+) -> ([0-9.]+)', window).groups()
    return (float(re.search(r'(?:step|;) loss ([0-9.]+)', first).group(1)),
            float(a), float(b))


@pytest.fixture(scope='module')
def one_device_losses():
    """The mesh cells' job (4 x 16 tokens a step, the same seed) through
    drivers/train.py on one device."""
    import contextlib
    import io
    import jax
    from benchmark import flops
    run = _load_run()
    run.require_devices = lambda chips: (jax.devices(),
                                         flops.peaks_for('TPU v5 lite'))
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(['--workload', 'toy-train-4', '--seed', '11',
                       '--seconds', '0.2', '--trace', '0'],
                      manifest_path=TOY_MANIFEST)
    assert rc == 0
    return _losses(out.getvalue().splitlines())


@pytest.mark.parametrize('trace', [0, 1])
def test_train_mesh_on_four_devices(run_on_cpu, capsys, one_device_losses,
                                    trace):
    layout = 'with_data_parallel over 4 chips, Reduce'
    rc = run_on_cpu.main(['--workload', 'toy-train-mesh', '--seed', '11',
                          '--seconds', '0.2', '--trace', str(trace)],
                         manifest_path=TOY_MANIFEST)
    out, lines = _last_json(capsys)
    assert rc == 0 and set(out) - {'breakdown'} == CONTRACT_KEYS
    assert out['correct'] is True and out['failed'] == 0
    assert out['attempted'] > 0
    if trace:
        # resident_hbm_gb has nothing to read on the CPU, and its trace
        # names no collective (test_bench_reduce_trace.py has the reader)
        assert 'peak_hbm_gb.train' not in out['metrics']
        assert out['metrics']['train_step_mfu']['value'] < 100
    else:
        assert set(out['metrics']) == {'train_tokens_per_s', 'setup_s'}
    assert all(v['value'] > 0 for k, v in out['metrics'].items()
               if k != 'pallas_time_share')
    assert any('layout ' + layout in ln for ln in lines)
    # born sharded, on every device of the mesh, and one lowering
    assert any('parameters not on all 4 chips: 0' in ln for ln in lines)
    assert any('one lowering of the step: True; state spread: True' in ln
               for ln in lines)
    facts = json.loads([ln for ln in lines
                        if ln.startswith('facts: ')][0][len('facts: '):])
    assert facts['chips'] == 4 and layout in facts['layout']
    # the first step's gradients and Adam update were held to the
    # reference, each reading printed beside its limit
    step = [ln for ln in lines if 'first train step vs reference' in ln][0]
    for name in ('grad_seq_weight_err', 'grad_bf16_residual',
                 'adam_moment2_rms', 'adam_param_rms'):
        assert re.search(name + r' [0-9.e+-]+ \((max|min) ', step)
    # the first step's loss, and the window's first (step 3; a traced run
    # has made other steps by then), are the one-device run's, to
    # chip_smoke.py's tolerance
    got, ref = _losses(lines), one_device_losses
    assert got[0] == pytest.approx(ref[0], abs=0.01)
    if not trace:
        assert got[1] == pytest.approx(ref[1], abs=0.01)


def test_train_mesh_refuses_a_layout_that_is_not_the_cells_chips():
    from benchmark.drivers import train_mesh
    import jax
    for runner in ('mesh', 'pipeline'):
        with pytest.raises(ValueError):
            train_mesh.Layout({'runner': runner}, jax.devices()[:4])


def test_without_a_tpu_it_exits_non_zero_and_prints_no_result(capsys):
    run = _load_run()
    with pytest.raises(SystemExit) as e:
        run.main(['--workload', 'fd355m-train-2k', '--seed', '1',
                  '--seconds', '1', '--trace', '0'])
    assert e.value.code not in (0, None)
    assert not [ln for ln in capsys.readouterr().out.splitlines()
                if ln.lstrip().startswith('{')]


def test_an_unknown_workload_is_refused(capsys):
    run = _load_run()
    with pytest.raises(SystemExit) as e:
        run.main(['--workload', 'nope', '--seed', '1', '--seconds', '1',
                  '--trace', '0'])
    assert e.value.code not in (0, None)


# ---- the manifest -------------------------------------------------------

# Every check of the manifest takes the manifest as an argument and finds
# its entries by `name` (`by_name`), never by position: a later PR appends
# configurations, cells and metrics, and test_bench_manifest.py runs all
# of these checks on a copy with such entries appended.

with open(os.path.join(ROOT, 'BENCHMARK.json')) as _f:
    MANIFEST = json.load(_f)
METRICS = MANIFEST['end_to_end'] + MANIFEST['per_layer']


def by_name(entries, name):
    """The one entry of a manifest list that has this `name`."""
    entry, = [x for x in entries if x['name'] == name]
    return entry


def check_manifest_keys_and_limits(manifest):
    assert set(manifest) == {'command', 'paths', 'run_seconds', 'configs',
                             'workloads', 'end_to_end', 'per_layer'}
    assert 1 <= manifest['run_seconds'] <= 51
    assert all(PATH.match(p) and os.path.isdir(os.path.join(ROOT, p))
               for p in manifest['paths'])
    assert len(json.dumps(manifest)) < 64 * 1024
    assert 1 <= len(manifest['configs']) <= 24
    assert 1 <= len(manifest['workloads']) <= 24
    assert 1 <= len(manifest['end_to_end']) <= 16
    assert 1 <= len(manifest['per_layer']) <= 128
    four = [w for w in manifest['workloads'] if w['chips'] == 4]
    assert len(four) <= max(1, len(manifest['workloads']) // 4)
    for section in ('configs', 'workloads'):
        names = [x['name'] for x in manifest[section]]
        assert len(names) == len(set(names))
    pairs = [(w['config'], w['traffic']) for w in manifest['workloads']]
    assert len(pairs) == len(set(pairs))
    names = [x['name'] for x in manifest['end_to_end']
             + manifest['per_layer']]
    assert len(names) == len(set(names))
    assert 'setup_s' in [x['name'] for x in manifest['end_to_end']]
    for x in manifest['end_to_end']:
        assert set(x) - {'workloads'} == {'name', 'unit', 'better', 'bound',
                                          'source'}
        assert 0.01 <= x['bound'] <= 0.1
        assert x['source'] in ('host_clock', 'device_trace')


def test_manifest_keys_and_limits():
    check_manifest_keys_and_limits(MANIFEST)
    assert os.path.getsize(os.path.join(ROOT, 'BENCHMARK.json')) < 64 * 1024


def check_cell_resolves_to_files(cell, manifest):
    run = _load_run()
    assert set(cell) == {'name', 'config', 'traffic', 'chips', 'why'}
    assert NAME.match(cell['name']) and NAME.match(cell['traffic'])
    assert cell['chips'] in (1, 4) and 1 <= len(cell['why']) <= 200
    _cell, config, traffic = run.load_cell(manifest, cell['name'])
    assert os.path.isfile(run.find_file(manifest, 'drivers',
                                        traffic['kind'] + '.py'))
    assert os.path.isfile(run.find_file(manifest, 'models',
                                        config['builder'] + '.py'))
    e2e = run.metrics_of(manifest, 'end_to_end', cell['name'])
    assert {'setup_s'} < {x['name'] for x in e2e}
    layer = run.metrics_of(manifest, 'per_layer', cell['name'])
    assert layer
    for x in layer:
        assert x['moves'] in {y['name'] for y in e2e}
        reader = run.load_module(run.find_file(manifest, 'layer_metrics',
                                               x['name'] + '.py'))
        assert callable(reader.read)


@pytest.mark.parametrize('cell', MANIFEST['workloads'],
                         ids=lambda c: c['name'])
def test_cell_resolves_to_files(cell):
    check_cell_resolves_to_files(cell, MANIFEST)


def check_config_entry(conf, manifest):
    """One entry of `configs` against its file. `reduced` lists the keys
    cut from the source (names: the manifest's contract allows no space
    there), the same in the manifest and in the file; the file gives each
    one's published value under `reduced_from` and, where anything is cut,
    the `deployment` the cut stands for. The shapes are checked by the
    configuration's own model file, whatever its keys."""
    run = _load_run()
    assert set(conf) == {'name', 'source', 'file', 'reduced', 'why'}
    assert NAME.match(conf['name']) and 1 <= len(conf['source']) <= 200
    assert any(conf['file'].startswith(p + '/') for p in manifest['paths'])
    with open(os.path.join(ROOT, conf['file'])) as f:
        body = json.load(f)
    reduced = conf['reduced']
    assert isinstance(reduced, list) and len(reduced) <= 16
    assert all(isinstance(k, str) and NAME.match(k) for k in reduced)
    assert body['reduced'] == reduced
    if reduced:
        assert isinstance(body.get('deployment'), str) \
            and body['deployment'].strip()
        assert set(body.get('reduced_from', {})) == set(reduced)
        assert all(k in body and body[k] != body['reduced_from'][k]
                   for k in reduced)
    model = run.load_module(run.find_file(manifest, 'models',
                                          body['builder'] + '.py'))
    shapes = model.param_shapes(body)
    assert shapes and all(len(s) > 0 and all(d > 0 for d in s)
                          for s in shapes.values())
    assert conf['name'] in {w['config'] for w in manifest['workloads']}


@pytest.mark.parametrize('conf', MANIFEST['configs'], ids=lambda c: c['name'])
def test_config_entry(conf):
    check_config_entry(conf, MANIFEST)


# a depth-cut configuration of another family (tests/benchmark_tests/
# configs/toy-cut.json, models/toy_mlp.py), as a manifest would list it
CUT = {'name': 'toy-cut', 'source': 'none',
       'file': 'tests/benchmark_tests/configs/toy-cut.json',
       'reduced': ['num_hidden_layers'], 'why': 'fixture'}
CUT_MANIFEST = {'paths': MANIFEST['paths'],
                'workloads': [{'config': 'toy-cut'}]}


def _cut_file(tmp_path, **changes):
    """toy-cut.json with `changes` (None drops the key), written under
    the benchmark's own paths so that the entry's `file` check holds."""
    with open(os.path.join(ROOT, CUT['file'])) as f:
        body = json.load(f)
    for k, v in changes.items():
        if v is None:
            body.pop(k)
        else:
            body[k] = v
    path = tmp_path / 'cut.json'
    path.write_text(json.dumps(body))
    return os.path.relpath(str(path), ROOT)


def test_config_entry_admits_a_cut_configuration_of_another_family():
    check_config_entry(CUT, CUT_MANIFEST)


@pytest.mark.parametrize('conf_changes,file_changes', [
    ({'reduced': []}, {}),
    ({'reduced': ['num_hidden_layers 16 -> 6']},
     {'reduced': ['num_hidden_layers 16 -> 6']}),
    ({}, {'deployment': None}),
    ({}, {'deployment': '  '}),
    ({}, {'reduced_from': None}),
    ({}, {'reduced_from': {'num_hidden_layers': 6}}),
    ({}, {'hidden_size': 0}),
], ids=['reduced-differs', 'reduced-not-a-key-name', 'no-deployment',
        'blank-deployment', 'no-published-value', 'nothing-cut',
        'shape-not-positive'])
def test_config_entry_refuses(tmp_path, conf_changes, file_changes):
    conf = dict(CUT, **conf_changes)
    if file_changes:
        conf['file'] = _cut_file(tmp_path, **file_changes)
        # the written copy lies outside `paths`: admit its directory
        manifest = dict(CUT_MANIFEST, paths=MANIFEST['paths'] + [
            os.path.dirname(conf['file'])])
    else:
        manifest = CUT_MANIFEST
    with pytest.raises(AssertionError):
        check_config_entry(conf, manifest)


def check_metric_names_and_units(metric, manifest):
    assert NAME.match(metric['name']) and UNIT.match(metric['unit'])
    assert metric['better'] in ('lower', 'higher')
    assert metric['source'] in SOURCES
    cells = [w['name'] for w in manifest['workloads']]
    listed = metric.get('workloads', cells)
    assert set(listed) <= set(cells) and len(listed) == len(set(listed))
    if 'layer' in metric:
        assert set(metric) - {'workloads'} == {'name', 'unit', 'better',
                                               'source', 'layer', 'moves'}
        assert '\n' not in metric['layer'] and len(metric['layer']) <= 200


@pytest.mark.parametrize('metric', METRICS, ids=lambda m: m['name'])
def test_metric_names_and_units(metric):
    check_metric_names_and_units(metric, MANIFEST)


def test_size_train_mesh_compiles_the_toy_step_for_the_v5e_without_a_chip():
    """benchmark/size_train_mesh.py in a process of its own (it sets the
    device-less TPU topology's environment): the toy mesh cell's train
    step through XLA:TPU and Mosaic, bytes a chip holds."""
    import subprocess
    import sys
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'benchmark',
                                      'size_train_mesh.py'),
         '--config', os.path.join(HERE, 'configs', 'toy-lm.json'),
         '--traffic', os.path.join(HERE, 'traffic', 'toy-train-mesh.json')],
        capture_output=True, text=True, timeout=240,
        env=dict(os.environ, JAX_PLATFORMS='cpu'))
    assert done.returncode == 0, done.stderr[-2000:]
    out = json.loads(done.stdout.strip().splitlines()[-1])
    assert 'Reduce' in out['layout'] and out['sequences_per_step'] == 4
    assert out['temp_bytes'] > 0 and out['mosaic_calls'] > 0
    # donation: what goes in as state comes out in the same bytes
    assert 0 < out['alias_bytes'] <= out['argument_bytes']
    assert out['per_chip_gb'] == pytest.approx(
        (out['argument_bytes'] + out['output_bytes'] - out['alias_bytes']
         + out['temp_bytes']) / 1e9, abs=1e-3)
