"""The per-layer metrics of set-up (benchmark/setup_stages.py and its nine
readers) and of the SPMD runners' host phases (four readers over
phase_counters.run_host_ms): each on counters booked by the test, nothing
on an empty registry, and their entries in the manifest with the cells
they list."""
import os

import pytest

from benchmark import setup_stages
from test_bench_run import MANIFEST, ROOT, _load_run, by_name

SERIES = setup_stages.SERIES
RUN = 'executor_run_phase_seconds_total'
MESH_CELL = 'fd1.3b-train-4chip'
# (the other three cells' own test files hold what may list them — the SET
# of metrics in test_bench_joyai.py and test_bench_lfm2.py, that each moves
# itl_p95_ms in test_bench_kexaone.py: the readers read there all the same,
# and nothing here stops the `benchmark` PR that appends them)
LISTED = ['fd355m-train-2k', 'fd355m-serve-chat', 'fd1.3b-serve-doc',
          MESH_CELL, 'olmoe-serve-chat16', 'jamba2-serve-reason128',
          'nemotron3-serve-reason128', 'mellum2-serve-code64']

# seconds a (stage, program)
BOOKED = {
    ('import', None): 2.0, ('build', 'lm_train'): 3.0,
    ('trace', 'lm_train'): 4.0, ('trace', 'lm_eval'): 1.0,
    ('lower', 'lm_train'): 0.5, ('compile', 'lm_train'): 0.25,
    ('cache_load', 'lm_train'): 1.5, ('cache_load', 'run.prepare'): 0.5,
    ('place', 'lm_train'): 0.75, ('first_run', 'lm_train'): 2.5,
    ('first_run', 'generate.warmup'): 1.0,
}
SETUP = {'setup_program_s': 17.0, 'setup_program_s.import': 2.0,
         'setup_program_s.build': 3.0, 'setup_program_s.trace': 5.0,
         'setup_program_s.lower': 0.5, 'setup_program_s.compile': 0.25,
         'setup_program_s.cache_load': 2.0, 'setup_program_s.place': 0.75,
         'setup_program_s.first_run': 3.5}
LAYERS = {'setup_program_s': 'trainer API',
          'setup_program_s.import': 'trainer API',
          'setup_program_s.build': 'model step',
          'setup_program_s.trace': 'model step',
          'setup_program_s.lower': 'model step',
          'setup_program_s.compile': 'trainer API',
          'setup_program_s.cache_load': 'trainer API',
          'setup_program_s.place': 'trainer API',
          'setup_program_s.first_run': 'trainer API'}

TRAIN = {'counters': {
    RUN + '{phase=prepare}': 0.2, RUN + '{phase=dispatch}': 0.5,
    RUN + '{phase=commit}': 0.1, RUN + '{phase=fetch}': 30.0,
    'executor_run_total': 100}}
MESH = {'mesh_run_host_ms': 8.0, 'mesh_run_host_ms.prepare': 2.0,
        'mesh_run_host_ms.dispatch': 5.0, 'mesh_run_host_ms.commit': 1.0}


def _reader(name):
    return _load_run().load_module(
        os.path.join(ROOT, 'benchmark', 'layer_metrics', name + '.py'))


@pytest.fixture()
def registry():
    """The program's registry, empty for the test and as it was after."""
    from paddle_tpu import monitor
    with monitor._lock:
        kept = {n: dict(s) for n, s in monitor._counters.items()}
        monitor._counters.clear()
    yield monitor
    with monitor._lock:
        monitor._counters.clear()
        monitor._counters.update(kept)


@pytest.fixture()
def booked(registry):
    from paddle_tpu import coldstart
    for (stage, program), seconds in BOOKED.items():
        coldstart.book(stage, seconds, program)
    return registry


@pytest.mark.parametrize('name', sorted(SETUP))
def test_setup_reader_value(booked, name):
    # facts['counters'] is the window's movement: set-up is not in it, and
    # the reader does not look there
    facts = {'counters': {SERIES + '{program=lm_train,stage=trace}': 99.0}}
    assert _reader(name).read(facts) == pytest.approx(SETUP[name])


def test_the_parts_sum_to_the_whole(booked):
    parts = [_reader(n).read({}) for n in SETUP if n != 'setup_program_s']
    assert sum(parts) == pytest.approx(_reader('setup_program_s').read({}))


@pytest.mark.parametrize('name', sorted(SETUP))
def test_setup_reader_reads_nothing_on_an_empty_registry(registry, name):
    assert _reader(name).read({'counters': {}}) is None
    # ... nor where the program books other things and not the series, as
    # the parent of the PR that brought the stages does
    registry.inc('executor_run_total')
    assert _reader(name).read({'counters': {}}) is None


def test_a_stage_nobody_booked_reads_zero_beside_the_others(registry):
    from paddle_tpu import coldstart
    coldstart.book('trace', 1.0, 'lm_train')
    assert _reader('setup_program_s.compile').read({}) == 0.0
    assert _reader('setup_program_s').read({}) == 1.0


def test_labels_of_a_key():
    assert setup_stages.labels_of(
        SERIES + '{program=lm_prefill_paged_b512,stage=first_run}') == {
            'program': 'lm_prefill_paged_b512', 'stage': 'first_run'}
    assert setup_stages.labels_of(SERIES + '{stage=import}') == {
        'stage': 'import'}
    assert setup_stages.stage_seconds({'executor_run_total': 3}) is None


@pytest.mark.parametrize('name', sorted(MESH))
def test_mesh_reader_value(name):
    assert _reader(name).read(TRAIN) == pytest.approx(MESH[name])


@pytest.mark.parametrize('name', sorted(MESH))
def test_mesh_reader_reads_nothing_without_its_counters(name):
    read = _reader(name).read
    assert read({}) is None and read({'counters': {}}) is None
    assert read({'counters': {'executor_run_total': 100}}) is None
    assert read({'counters': dict(TRAIN['counters'],
                                  executor_run_total=0)}) is None


def check_setup_entries(manifest):
    cells = {w['name'] for w in manifest['workloads']}
    for name, layer in LAYERS.items():
        spec = by_name(manifest['per_layer'], name)
        assert spec == dict(spec, unit='s', better='lower',
                            source='program_counter', layer=layer,
                            moves='setup_s')
        # an explicit list: a metric without one would be asked of every
        # cell a later PR adds. At least the eight: a later PR may append
        assert set(LISTED) <= set(spec['workloads']) <= cells
    for name in MESH:
        spec = by_name(manifest['per_layer'], name)
        assert spec == dict(spec, unit='ms', better='lower',
                            source='program_counter', layer='SPMD runners',
                            moves='train_tokens_per_s')
        assert MESH_CELL in spec['workloads']
        assert set(spec['workloads']) <= cells


def test_the_manifest_has_the_thirteen_entries():
    check_setup_entries(MANIFEST)
    # every cell reports setup_s, the one end-to-end metric with no list
    assert 'workloads' not in by_name(MANIFEST['end_to_end'], 'setup_s')
    names = {x['name'] for x in MANIFEST['per_layer']}
    assert set(SETUP) | set(MESH) <= names


@pytest.mark.parametrize('name', sorted(set(SETUP) | set(MESH)))
def test_each_entry_has_its_reader_file(name):
    assert os.path.isfile(os.path.join(ROOT, 'benchmark', 'layer_metrics',
                                       name + '.py'))
