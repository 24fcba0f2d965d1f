"""BENCHMARK.json takes entries at the END of its lists, and the tests find
an entry by its name. Held here: the order recorded in
fixtures/manifest_order.json is a prefix of the manifest's (a PR that
appends passes without touching the fixture; one that inserts, reorders
or removes fails; a `benchmark` PR that prunes rewrites the fixture); no
test file of this directory subscripts a list of the real manifest by
position; the four per-layer entries PR 34 brought; and a rehearsal of
the next `model_config` PR on no chip — the real manifest with a
configuration, a cell and a per-layer metric appended passes every
manifest-level check of the suite and runs its cell through run.py."""
import ast
import copy
import glob
import json
import os

import pytest

import test_bench_joyai
import test_bench_phase_metrics
import test_bench_sampled_step_share
from test_bench_run import (HERE, MANIFEST, ROOT, _last_json,  # noqa: F401
                            by_name, check_cell_resolves_to_files,
                            check_config_entry,
                            check_manifest_keys_and_limits,
                            check_metric_names_and_units, run_on_cpu)

SECTIONS = ('configs', 'workloads', 'end_to_end', 'per_layer')
with open(os.path.join(HERE, 'fixtures', 'manifest_order.json')) as _f:
    RECORDED = json.load(_f)


# ---- appended, never put in ---------------------------------------------

def order_of(manifest):
    """What fixtures/manifest_order.json records: the names of each list
    in order and, a metric, the cells it lists (null: every cell)."""
    order = {s: [x['name'] for x in manifest[s]] for s in SECTIONS}
    order['metric_workloads'] = {
        x['name']: x.get('workloads')
        for x in manifest['end_to_end'] + manifest['per_layer']}
    return order


def check_manifest_order(manifest, recorded):
    now = order_of(manifest)
    for section in SECTIONS:
        was = recorded[section]
        assert now[section][:len(was)] == was, section
    for name, was in recorded['metric_workloads'].items():
        cells = now['metric_workloads'][name]
        assert (cells is None) if was is None \
            else cells[:len(was)] == was, name


def test_the_recorded_order_is_a_prefix_of_the_manifests():
    check_manifest_order(MANIFEST, RECORDED)
    # the fixture is a record of the manifest, not a list of its own
    assert set(RECORDED) == set(SECTIONS) | {'metric_workloads'}
    assert set(RECORDED['metric_workloads']) \
        == set(RECORDED['end_to_end'] + RECORDED['per_layer'])


# ---- no test finds an entry of the real manifest by position ------------

LISTS = set(SECTIONS)


def _is_manifest_list(node, aliases):
    """`<anything>['configs' | 'workloads' | 'end_to_end' | 'per_layer']`
    (a list of the manifest, or a metric's `workloads`), METRICS, or a
    name assigned from one of those; a base whose name says `toy` is a
    fixture's manifest and exempt."""
    if isinstance(node, ast.Name):
        return node.id == 'METRICS' or node.id in aliases
    if not (isinstance(node, ast.Subscript)
            and isinstance(node.slice, ast.Constant)
            and node.slice.value in LISTS):
        return False
    base = node.value
    return not (isinstance(base, ast.Name) and 'toy' in base.id.lower())


def _is_position(index):
    """An integer, a negative one, or a slice with an integer bound."""
    if isinstance(index, ast.Slice):
        return any(b is not None and _is_position(b)
                   for b in (index.lower, index.upper))
    if isinstance(index, ast.UnaryOp) and isinstance(index.op, ast.USub):
        index = index.operand
    return isinstance(index, ast.Constant) \
        and isinstance(index.value, int) \
        and not isinstance(index.value, bool)


def position_pins(source):
    """The lines of a test file's source that subscript a list of the
    manifest by position."""
    tree = ast.parse(source)
    aliases = {t.id for node in ast.walk(tree)
               if isinstance(node, ast.Assign)
               and _is_manifest_list(node.value, ())
               for t in node.targets if isinstance(t, ast.Name)}
    return sorted(node.lineno for node in ast.walk(tree)
                  if isinstance(node, ast.Subscript)
                  and _is_position(node.slice)
                  and _is_manifest_list(node.value, aliases))


def test_no_test_subscripts_a_list_of_the_manifest_by_position():
    files = sorted(glob.glob(os.path.join(HERE, 'test_*.py')))
    assert len(files) >= 11
    pins = {}
    for path in files:
        with open(path) as f:
            found = position_pins(f.read())
        if found:
            pins[os.path.basename(path)] = found
    assert not pins, 'find the entry by its name (by_name): %r' % pins


@pytest.mark.parametrize('line,pinned', [
    # the three pins of test_bench_joyai.py and the one of
    # test_bench_sampled_step_share.py, as they stood before PR 34
    ("assert MANIFEST['configs'][-1] is conf", True),
    ("cell = MANIFEST['workloads'][-1]", True),
    ("ok = all(x['workloads'][-1] == CELL for x in METRICS)", True),
    ("entry = manifest['per_layer'][-1]", True),
    ("cells = manifest['workloads']\nlast = cells[-1]", True),
    ("first = METRICS[0]", True),
    ("newest = MANIFEST['per_layer'][-4:]", True),
    ("conf = by_name(MANIFEST['configs'], 'joyai-llm-flash-ep4')", False),
    ("serve = by_name(manifest['end_to_end'], 'itl_p95_ms')['workloads']",
     False),
    ("cell = toy['workloads'][0]", False),
    ("last = lines[-1]", False),
    ("was = now[section][:len(recorded)]", False),
], ids=['configs-last', 'workloads-last', 'a-metrics-cells-last',
        'per-layer-last', 'through-a-name', 'metrics-first', 'a-slice',
        'by-name', 'by-name-then-a-key', 'a-toy-manifest', 'another-list',
        'a-prefix-by-length'])
def test_the_lint_tells_a_position_from_a_name(line, pinned):
    assert bool(position_pins(line)) is pinned


# ---- the four entries PR 34 brought --------------------------------------

SERVE = ['fd355m-serve-chat', 'fd1.3b-serve-doc', 'olmoe-serve-chat16',
         'joyai-serve-longchat64']
JOYAI = ['joyai-serve-longchat64']
ENTRIES = [
    {'name': 'decode_overlapped_step_share', 'unit': '%',
     'better': 'higher', 'source': 'program_counter', 'layer': 'server',
     'moves': 'serve_tokens_per_s', 'workloads': SERVE},
    {'name': 'mla_decode_attention_hbm_share', 'unit': '%',
     'better': 'higher', 'source': 'device_trace', 'layer': 'kernels',
     'moves': 'serve_tokens_per_s', 'workloads': JOYAI},
    {'name': 'moe_held_assignment_share', 'unit': '%', 'better': 'lower',
     'source': 'program_counter', 'layer': 'model step',
     'moves': 'serve_tokens_per_s', 'workloads': JOYAI},
    {'name': 'moe_held_ffn_hbm_share', 'unit': '%', 'better': 'higher',
     'source': 'device_trace', 'layer': 'kernels',
     'moves': 'serve_tokens_per_s', 'workloads': JOYAI},
]


def check_per_layer_entry(entry, manifest):
    """The whole entry by name; a cell appended to its list since is the
    order test's to hold."""
    got = by_name(manifest['per_layer'], entry['name'])
    listed = got['workloads'][:len(entry['workloads'])]
    assert dict(got, workloads=listed) == entry
    assert os.path.isfile(os.path.join(
        ROOT, 'benchmark', 'layer_metrics', entry['name'] + '.py'))


@pytest.mark.parametrize('entry', ENTRIES, ids=lambda e: e['name'])
def test_manifest_entry(entry):
    check_per_layer_entry(entry, MANIFEST)


# ---- a rehearsal of the next model_config PR ------------------------------

FIFTH = {'name': 'rehearsal-fifth-config', 'source': 'none',
         'file': 'tests/benchmark_tests/configs/toy-joyai.json',
         'reduced': ['n_routed_experts'], 'why': 'rehearsal'}
SEVENTH = {'name': 'rehearsal-seventh-cell', 'config': FIFTH['name'],
           'traffic': 'toy-serve-joyai', 'chips': 1, 'why': 'rehearsal'}
READER = {'name': 'toy_tokens_per_decode_step', 'unit': 'tokens',
          'better': 'higher', 'source': 'program_counter',
          'layer': 'server', 'moves': 'serve_tokens_per_s',
          'workloads': [SEVENTH['name']]}
BESIDE = 'joyai-serve-longchat64'


def appended(manifest, cell_at=None):
    """A copy with what the next `model_config` PR brings: a fifth
    configuration, a seventh cell on it (at the end; `cell_at`: put in
    at that place), the cell at the end of the list of every metric that
    lists the JoyAI cell, and a per-layer entry with its reader."""
    m = copy.deepcopy(manifest)
    m['configs'].append(dict(FIFTH))
    m['workloads'].insert(len(m['workloads']) if cell_at is None
                          else cell_at, dict(SEVENTH))
    for x in m['end_to_end'] + m['per_layer']:
        if BESIDE in x.get('workloads', ()):
            x['workloads'].append(SEVENTH['name'])
    m['per_layer'].append(copy.deepcopy(READER))
    return m


def check_every_manifest_level_check(manifest):
    check_manifest_keys_and_limits(manifest)
    for conf in manifest['configs']:
        check_config_entry(conf, manifest)
    for cell in manifest['workloads']:
        check_cell_resolves_to_files(cell, manifest)
    for metric in manifest['end_to_end'] + manifest['per_layer']:
        check_metric_names_and_units(metric, manifest)
    check_manifest_order(manifest, RECORDED)
    for entry in ENTRIES:
        check_per_layer_entry(entry, manifest)
    test_bench_joyai.check_joyai_entry(manifest)
    test_bench_sampled_step_share.check_manifest_entry(manifest)
    test_bench_phase_metrics.check_phase_metrics(manifest)


def test_the_manifest_takes_a_configuration_a_cell_and_a_metric_at_its_end():
    grown = appended(MANIFEST)
    assert [len(grown[s]) - len(MANIFEST[s]) for s in SECTIONS] \
        == [1, 1, 0, 1]
    check_every_manifest_level_check(grown)


def _index(manifest, section, name):
    return [x['name'] for x in manifest[section]].index(name)


def _swapped(manifest, section):
    m = copy.deepcopy(manifest)
    m[section].insert(0, m[section].pop())
    return m


def _without(manifest, section, name):
    m = copy.deepcopy(manifest)
    m[section].remove(by_name(m[section], name))
    return m


def _cell_put_in_a_metrics_list(manifest):
    m = appended(manifest)
    cells = by_name(m['per_layer'], 'decode_step_ms')['workloads']
    cells.insert(cells.index(BESIDE), cells.pop())
    return m


@pytest.mark.parametrize('broken', [
    lambda m: appended(m, cell_at=_index(m, 'workloads', BESIDE)),
    _cell_put_in_a_metrics_list,
    lambda m: _swapped(m, 'configs'),
    lambda m: _swapped(m, 'per_layer'),
    lambda m: _without(m, 'per_layer', 'moe_held_ffn_hbm_share'),
    lambda m: _without(m, 'end_to_end', 'ttft_p95_ms'),
], ids=['cell-put-in-before-the-last', 'cell-put-in-a-metrics-list',
        'configs-reordered', 'per-layer-reordered', 'a-metric-removed',
        'an-end-to-end-metric-removed'])
def test_the_order_test_refuses(broken):
    with pytest.raises((AssertionError, KeyError)):
        check_manifest_order(broken(MANIFEST), RECORDED)


def test_the_appended_cell_runs_through_run_py(run_on_cpu,  # noqa: F811
                                               capsys, tmp_path):
    path = tmp_path / 'BENCHMARK.json'
    path.write_text(json.dumps(appended(MANIFEST)))
    rc = run_on_cpu.main(['--workload', SEVENTH['name'], '--seed',
                          '3000000034', '--seconds', '0.7', '--trace', '1'],
                         manifest_path=str(path))
    out, _lines = _last_json(capsys)
    assert rc == 0 and out['correct'] is True
    # what the JoyAI cell lists and a CPU run can read, and the reader the
    # rehearsal brought under tests/benchmark_tests/layer_metrics
    assert {READER['name'], 'decode_step_ms', 'decode_overlapped_step_share',
            'moe_held_assignment_share'} <= set(out['metrics'])
    assert out['metrics'][READER['name']]['unit'] == 'tokens'
    assert out['metrics'][READER['name']]['value'] > 0
    assert 0 < out['metrics']['decode_overlapped_step_share']['value'] <= 100
