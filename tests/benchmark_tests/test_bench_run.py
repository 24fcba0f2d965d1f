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


@pytest.fixture()
def run_on_cpu(monkeypatch):
    """run.py with its platform check and its choice of trace line
    overridden for the CPU backend — in the test, not in the program."""
    import jax
    from benchmark import flops, reduce_trace
    run = _load_run()
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
def test_traced_line(run_on_cpu, capsys, workload, metrics):
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
    assert not os.path.exists(os.path.join(ROOT, '.bench_trace', workload))


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

with open(os.path.join(ROOT, 'BENCHMARK.json')) as _f:
    MANIFEST = json.load(_f)
METRICS = MANIFEST['end_to_end'] + MANIFEST['per_layer']


def test_manifest_keys_and_limits():
    assert set(MANIFEST) == {'command', 'paths', 'run_seconds', 'configs',
                             'workloads', 'end_to_end', 'per_layer'}
    assert 1 <= MANIFEST['run_seconds'] <= 51
    assert all(PATH.match(p) and os.path.isdir(os.path.join(ROOT, p))
               for p in MANIFEST['paths'])
    assert os.path.getsize(os.path.join(ROOT, 'BENCHMARK.json')) < 64 * 1024
    four = [w for w in MANIFEST['workloads'] if w['chips'] == 4]
    assert len(four) <= max(1, len(MANIFEST['workloads']) // 4)
    names = [x['name'] for x in METRICS]
    assert len(names) == len(set(names))
    assert 'setup_s' in [x['name'] for x in MANIFEST['end_to_end']]
    for x in MANIFEST['end_to_end']:
        assert set(x) - {'workloads'} == {'name', 'unit', 'better', 'bound',
                                          'source'}
        assert 0.01 <= x['bound'] <= 0.1
        assert x['source'] in ('host_clock', 'device_trace')


@pytest.mark.parametrize('cell', MANIFEST['workloads'],
                         ids=lambda c: c['name'])
def test_cell_resolves_to_files(cell):
    run = _load_run()
    assert set(cell) == {'name', 'config', 'traffic', 'chips', 'why'}
    assert NAME.match(cell['name']) and NAME.match(cell['traffic'])
    assert cell['chips'] in (1, 4) and 1 <= len(cell['why']) <= 200
    _cell, config, traffic = run.load_cell(MANIFEST, cell['name'])
    assert os.path.isfile(run.find_file(MANIFEST, 'drivers',
                                        traffic['kind'] + '.py'))
    assert os.path.isfile(run.find_file(MANIFEST, 'models',
                                        config['builder'] + '.py'))
    e2e = run.metrics_of(MANIFEST, 'end_to_end', cell['name'])
    assert {'setup_s'} < {x['name'] for x in e2e}
    layer = run.metrics_of(MANIFEST, 'per_layer', cell['name'])
    assert layer
    for x in layer:
        assert x['moves'] in {y['name'] for y in e2e}
        reader = run.load_module(run.find_file(MANIFEST, 'layer_metrics',
                                               x['name'] + '.py'))
        assert callable(reader.read)


@pytest.mark.parametrize('conf', MANIFEST['configs'], ids=lambda c: c['name'])
def test_config_entry(conf):
    assert set(conf) == {'name', 'source', 'file', 'reduced', 'why'}
    assert NAME.match(conf['name']) and 1 <= len(conf['source']) <= 200
    assert any(conf['file'].startswith(p + '/') for p in MANIFEST['paths'])
    with open(os.path.join(ROOT, conf['file'])) as f:
        body = json.load(f)
    assert body['reduced'] == conf['reduced'] == []
    assert body['head_dim'] * body['attention_heads'] == body['d_model']
    assert conf['name'] in {w['config'] for w in MANIFEST['workloads']}


@pytest.mark.parametrize('metric', METRICS, ids=lambda m: m['name'])
def test_metric_names_and_units(metric):
    assert NAME.match(metric['name']) and UNIT.match(metric['unit'])
    assert metric['better'] in ('lower', 'higher')
    assert metric['source'] in SOURCES
    cells = {w['name'] for w in MANIFEST['workloads']}
    assert set(metric.get('workloads', cells)) <= cells
    if 'layer' in metric:
        assert set(metric) - {'workloads'} == {'name', 'unit', 'better',
                                               'source', 'layer', 'moves'}
        assert '\n' not in metric['layer'] and len(metric['layer']) <= 200
