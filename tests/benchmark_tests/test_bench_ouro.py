"""The Ouro-2.6B configuration's benchmark files (ISSUE 63): a toy cell with
the new builder through run.py end to end on the CPU (its own toy manifest:
3 layers run 4 times a token), the manifest's entries and the published file
against the catalog's row, the cell's traffic letter for letter,
flops_ouro's formulae against a count of param_shapes and against the
issue's arithmetic, the four new readers on made-up facts of this
configuration (and nothing where there is nothing to read, a stall inside
the trace among them), and the comparison script's main() at toy width."""
import json
import os

import numpy as np
import pytest

from benchmark import flops_ouro
from benchmark.models import ouro

from test_bench_olmoe import _last_json, _load, run_on_cpu   # noqa: F401
from test_bench_run import MANIFEST, by_name, check_config_entry

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TOY_MANIFEST = os.path.join(HERE, 'fixtures', 'BENCHMARK.toy.ouro.json')
TOY_CONFIG = os.path.join(HERE, 'configs', 'toy-ouro.json')
TOY_TRAFFIC = os.path.join(HERE, 'traffic', 'toy-serve-ouro.json')
NAME = 'ouro-2.6b-l8'
CONFIG = os.path.join(ROOT, 'benchmark', 'configs', NAME + '.json')
CELL = 'ouro-serve-reason16'
CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'
NEW = ('loop_weight_stream_share', 'attention_kv_step_share.loop',
       'paged_decode_attention_roofline.loop', 'loop_pass_step_spread')
REDUCED = ['num_hidden_layers', 'layer_types', 'max_window_layers']
SETUP = {'setup_program_s'} | {'setup_program_s.' + s for s in (
    'import', 'build', 'trace', 'lower', 'compile', 'cache_load', 'place',
    'first_run')}
ITL = {'decode_step_ms', 'decode_host_gap_ms', 'decode_host_gap_ms.admit',
       'decode_host_gap_ms.feed', 'decode_host_gap_ms.dispatch',
       'decode_host_gap_ms.deliver', 'server_loop_unaccounted_share',
       'decode_sampled_step_share', 'admission_ms', 'admission_ms.dispatch',
       'admission_ms.drain', 'admission_ms.fetch', 'token_gap_ms.plain',
       'token_gap_ms.admission', 'token_gap_admission_share'}


def _json(path):
    with open(path) as f:
        return json.load(f)


# ---- the toy cell through run.py --------------------------------------------

def test_end_to_end_line(run_on_cpu, capsys):              # noqa: F811
    rc = run_on_cpu.main(['--workload', 'toy-serve-ouro', '--seed',
                          '3000000001', '--seconds', '2.0', '--trace', '0'],
                         manifest_path=TOY_MANIFEST)
    out, lines = _last_json(capsys)
    assert rc == 0 and out['correct'] is True and out['failed'] == 0
    assert out['attempted'] > 0
    assert set(out['metrics']) == {'serve_tokens_per_s', 'itl_p95_ms',
                                   'setup_s'}
    assert all(v['value'] > 0 for v in out['metrics'].values())
    check = [ln for ln in lines if 'check: prompt of' in ln]
    assert len(check) == 2 and all('generate_once: True' in ln
                                   for ln in check)


def test_traced_line(run_on_cpu, capsys):                  # noqa: F811
    rc = run_on_cpu.main(['--workload', 'toy-serve-ouro', '--seed', '7',
                          '--seconds', '2.0', '--trace', '1'],
                         manifest_path=TOY_MANIFEST)
    out, lines = _last_json(capsys)
    assert rc == 0 and out['correct'] is True
    # on the CPU no operation of the trace is a Mosaic kernel: the roofline
    # and the passes' spread return nothing
    assert set(out['metrics']) == {'decode_step_ms',
                                   'loop_weight_stream_share',
                                   'attention_kv_step_share.loop'}
    assert all(v['value'] > 0 for v in out['metrics'].values())
    facts = json.loads([ln for ln in lines
                        if ln.startswith('facts: ')][-1][len('facts: '):])
    assert facts['decode_bytes_per_step'] > 0


# ---- the manifest and the published file ------------------------------------

def check_ouro_entry(manifest):
    """The configuration, its cell and the metrics that list the cell,
    each found by name: where they stand in their lists is
    test_bench_manifest.py's to hold (appended, never put in)."""
    conf = by_name(manifest['configs'], NAME)
    check_config_entry(conf, manifest)
    assert conf['reduced'] == REDUCED
    assert conf['source'] == 'https://huggingface.co/ByteDance/' \
        'Ouro-2.6B/blob/main/config.json'
    cell = by_name(manifest['workloads'], CELL)
    assert cell == dict(cell, name=CELL, config=conf['name'],
                        traffic='reason16-closed', chips=1)
    listed = {x['name'] for x in manifest['end_to_end']
              + manifest['per_layer'] if CELL in x.get('workloads', ())}
    moved = {x['name']: x.get('moves') for x in manifest['per_layer']}
    # the cell reports at least one end-to-end metric beside setup_s, and
    # is listed under exactly the accepted metrics that move what it
    # reports and whose readers ask nothing of the configuration
    reports = listed & {'itl_p95_ms', 'serve_tokens_per_s'}
    assert reports
    assert listed >= SETUP | set(NEW)
    assert (ITL <= listed) == ('itl_p95_ms' in reports)
    tokens = {'ttft_p95_unbounded_ms', 'ttft_mean_unbounded_ms',
              'device_idle_share.serve', 'peak_hbm_gb.serve',
              'decode_overlapped_step_share', 'decode_hbm_share'}
    assert (tokens <= listed) == ('serve_tokens_per_s' in reports)
    assert {moved[n] for n in listed if n in moved} <= reports | {'setup_s'}
    # not under another family's readers, and not under the two lists that
    # test_bench_lfm2.py holds with `==` (M7)
    assert not {n for n in listed if n.startswith((
        'ssm_', 'ssd_', 'gdn_', 'mla_', 'kv_', 'moe_', 'window_', 'state_'))}
    assert not listed & {'paged_decode_attention_roofline',
                         'prefix_hit_token_share', 'attention_kv_step_share',
                         'ttft_p95_ms', 'prefill_ms.itl'}
    by = {x['name']: x for x in manifest['per_layer']}
    for name in NEW:
        assert by[name]['workloads'] == [CELL]
    assert len({by[n]['moves'] for n in NEW}) == 1
    assert {by[n]['moves'] for n in NEW} <= reports
    assert [by[n]['unit'] for n in NEW] == ['%', '%', '%', 'x']
    assert [by[n]['layer'] for n in NEW] == ['model step', 'model step',
                                             'kernels', 'model step']
    assert [by[n]['source'] for n in NEW] == [
        'program_counter', 'program_counter', 'device_trace', 'device_trace']


def test_config_entry_admits_the_new_entry():
    check_ouro_entry(MANIFEST)
    assert len(MANIFEST['workloads']) >= 14
    assert sum(w['chips'] == 4 for w in MANIFEST['workloads']) == 1


def test_the_manifest_with_these_entries_passes_every_manifest_check():
    """test_bench_manifest.py's checks, each a `check_*(.., manifest)`, on
    the manifest as this PR leaves it: the recorded order
    (fixtures/manifest_order.json, untouched) is a prefix of it, every
    entry's keys and limits hold, every cell resolves to its files."""
    from test_bench_manifest import (
        RECORDED, check_every_manifest_level_check, check_manifest_order,
        order_of)
    check_every_manifest_level_check(MANIFEST)
    check_manifest_order(MANIFEST, RECORDED)
    order = order_of(MANIFEST)
    assert NAME in order['configs'] and NAME not in RECORDED['configs']
    assert CELL in order['workloads'] and CELL not in RECORDED['workloads']
    fresh = [n for n in order['per_layer'] if n not in RECORDED['per_layer']]
    assert [n for n in fresh if n in NEW] == list(NEW)
    for name, cells in order['metric_workloads'].items():
        if cells and CELL in cells:
            # behind every cell the recorded list has
            known = RECORDED['metric_workloads'].get(name) or []
            assert cells.index(CELL) >= len(known), name


def test_the_cells_traffic_is_the_issues():
    tr = _json(os.path.join(ROOT, 'benchmark', 'traffic',
                            'reason16-closed.json'))
    assert tr['kind'] == 'serve' and tr['sampling'] == 'greedy'
    assert tr['arrival'] == {'kind': 'closed', 'clients': 16,
                             'stagger_s': 0.1}
    assert tr['prompt_len'] == {'dist': 'uniform', 'min': 64, 'max': 320}
    assert tr['output_len'] == {'dist': 'lognormal', 'median': 512,
                                'sigma': 0.5, 'min': 128, 'max': 1024}
    assert tr['pool_size'] == 128
    assert not tr.get('shared_prefix_len') and not tr.get('group_size')
    assert tr['engine'] == {'paged': True, 'slots': 16, 'block_size': 32,
                            'max_len': 1344,
                            'prompt_buckets': [128, 256, 512],
                            'num_blocks': 673}
    assert (tr['check_new_tokens'], tr['trace_seconds']) == (8, 8.0)
    e = tr['engine']
    # every slot at its longest fits the pool beside the trash block: no
    # request can meet cache_full; one prefill dispatch a prompt
    assert tr['prompt_len']['max'] + tr['output_len']['max'] == e['max_len']
    assert e['slots'] * e['max_len'] // e['block_size'] + 1 \
        == e['num_blocks']
    assert tr['prompt_len']['max'] <= e['prompt_buckets'][-1]
    assert tr['arrival']['clients'] == e['slots']
    m = _json(CONFIG)
    assert e['num_blocks'] * e['block_size'] * flops_ouro.kv_bytes_per_token(
        m) == pytest.approx(11.29e9, rel=1e-3)


def test_the_published_file_keeps_every_number_of_the_catalogs_row():
    if not os.path.isfile(CATALOG):
        pytest.skip('no catalog on this machine')
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f) if r['name'] == 'Ouro-2.6B']
    m = _json(CONFIG)
    changed = {k for k, v in row['config'].items() if m.get(k, '?') != v}
    # `layer_types` and `max_window_layers` are shortened WITH the depth
    # they describe, and are named for it
    assert changed == set(REDUCED) == set(m['reduced_from'])
    assert m['reduced'] == REDUCED
    assert m['reduced_from'] == {k: row['config'][k] for k in REDUCED}
    assert m['reduced_from']['num_hidden_layers'] == 48 \
        == len(m['reduced_from']['layer_types']) \
        == m['reduced_from']['max_window_layers']
    assert m['layer_types'] == ['full_attention'] * 8
    assert (m['num_hidden_layers'], m['max_window_layers']) == (8, 8)
    assert m['source'].startswith(row['source_url'])
    assert 'arXiv:2510.25741' in m['source']
    assert m['builder'] == 'ouro'
    # every width of the row
    assert (m['hidden_size'], m['intermediate_size'], m['head_dim'],
            m['num_attention_heads'], m['num_key_value_heads'],
            m['vocab_size'], m['total_ut_steps'], m['early_exit_threshold'],
            m['rms_norm_eps'], m['rope_theta'], m['rope_scaling'],
            m['tie_word_embeddings'], m['sliding_window'],
            m['use_sliding_window'], m['max_position_embeddings'],
            m['hidden_act'], m['model_type']) == \
        (2048, 5632, 128, 16, 16, 49152, 4, 1, 1e-6, 1000000, None, False,
         None, False, 65536, 'silu', 'ouro')
    assert 'FIRST 8 OF THE 48 LAYERS' in m['deployment']
    assert 'RING OF SIX' in m['deployment']
    assert set(m['assumed']) == {
        'looped_stack', 'cache_by_pass_and_layer', 'sandwich_norm',
        'exit_gate', 'rope', 'attention', 'ffn', 'final_norm'}
    assert 'float32' in m['changed']['serving_dtype']
    assert '1 344' in m['changed']['context']
    assert m.get('matmul_precision') in (None, 'highest')
    assert ('matmul_precision' in m) == ('matmul_precision' in m['changed'])


# ---- flops_ouro against a count of the parameters ---------------------------

def _count(shapes, pick=lambda name: True):
    return sum(int(np.prod(s)) for n, s in shapes.items() if pick(n))


@pytest.mark.parametrize('path', [CONFIG, TOY_CONFIG], ids=[NAME, 'toy'])
def test_flops_ouro_counts_what_param_shapes_lists(path):
    m = _json(path)
    f = flops_ouro
    shapes = ouro.param_shapes(m)
    assert f.param_count(m) == _count(shapes)
    for i in range(m['num_hidden_layers']):
        assert f.layer_param_count(m) == _count(
            shapes, lambda k: k.startswith('layer_%d.' % i))
    # the passes add cache layers and no parameter
    one = dict(m, total_ut_steps=2)
    assert _count(ouro.param_shapes(one)) == _count(shapes)
    cfg = ouro.lm_config(m, 32, False)
    from paddle_tpu.models import transformer as T
    pools = T.kv_cache_shapes(cfg, 4, 8, 1)
    assert pools[T.KV_CACHE_K][1] == f.cache_layers(m) \
        == m['total_ut_steps'] * m['num_hidden_layers']
    assert f.kv_bytes_per_token(m) == 2 * 4 * int(np.prod(
        pools[T.KV_CACHE_K][1:])) // 8
    # one row, no context: the layers' weights a pass, the final norm and
    # the gate with them, the head once, the table's one row
    d, v = m['hidden_size'], m['vocab_size']
    layers = _count(shapes, lambda k: k.startswith('layer_'))
    assert f.loop_weight_stream_bytes(m) == 4 * (
        m['total_ut_steps'] * (layers + 2 * d + 1) + d * v)
    assert f.decode_bytes_per_step(m, 0, 1) == \
        f.loop_weight_stream_bytes(m) + 4 * d
    assert f.decode_bytes_per_step(m, 100, 1) \
        - f.decode_bytes_per_step(m, 0, 1) == 100 * f.kv_bytes_per_token(m)
    assert f.paged_decode_attention_bytes(m, 7) == 7 * f.kv_row_bytes(m)


def test_the_published_configuration_is_what_the_issue_sized():
    m = _json(CONFIG)
    f = flops_ouro
    assert f.layer_param_count(m) == 51388416
    assert f.param_count(m) == 612438017
    assert 4 * f.param_count(m) == pytest.approx(2.45e9, rel=1e-3)
    whole = dict(m, num_hidden_layers=48)
    assert f.param_count(whole) == pytest.approx(2668.0e6, rel=1e-4)
    assert f.kv_row_bytes(m) == 16384
    assert f.kv_bytes_per_token(m) == 524288
    assert f.kv_bytes_per_token(whole) == 3145728        # "3.15 MB"
    assert 32 * f.kv_bytes_per_token(m) == pytest.approx(16.78e6, rel=1e-3)
    # the step the issue reckoned: 4 x 1.644 + 0.40 = 6.98 GB of weights,
    # 4.67 GB of K/V at ~8.9 k live tokens
    assert f.loop_weight_stream_bytes(m) == pytest.approx(6.98e9, rel=2e-3)
    assert 8900 * f.kv_bytes_per_token(m) == pytest.approx(4.67e9, rel=1e-3)
    cfg = ouro.lm_config(m, 1344, False)
    from paddle_tpu.models import transformer as T
    assert T.kv_cache_shapes(cfg, 673, 32, 16) == {
        'gen_kv_k': (673, 32, 32, 2048), 'gen_kv_v': (673, 32, 32, 2048)}
    assert (cfg.n_head, cfg.n_kv_head, cfg.head_dim, cfg.kv_width,
            cfg.attn_width, cfg.n_layer, cfg.passes, cfg.n_attn_layers) == \
        (16, 16, 128, 2048, 2048, 8, 4, 8)
    assert (cfg.matmul_precision, cfg.position, cfg.rope_theta, cfg.ffn,
            cfg.norm, cfg.rms_eps, cfg.bias, cfg.tie_embeddings,
            cfg.qk_norm, cfg.norm_placement, cfg.d_ff) == \
        (m.get('matmul_precision'), 'rope', 1e6, 'gated', 'rms_norm', 1e-6,
         False, False, False, 'sandwich', 5632)
    from paddle_tpu.ops import paged_decode_attention as pda
    assert pda.shapes_ok(16, 128, 32, 16)


def test_init_params_is_seeded_and_spreads_norms_and_the_gates_bias():
    m = _json(TOY_CONFIG)
    a = ouro.init_params(m, 3000000001)
    b = ouro.init_params(m, 3000000001)
    c = ouro.init_params(m, 5)
    assert sorted(a) == sorted(ouro.param_shapes(m))
    for name, shape in ouro.param_shapes(m).items():
        assert tuple(a[name].shape) == tuple(shape)
        np.testing.assert_array_equal(np.asarray(a[name]),
                                      np.asarray(b[name]))
    assert np.abs(np.asarray(a['tok_emb.w'])
                  - np.asarray(c['tok_emb.w'])).max() > 0
    for name in ('layer_0.ln1.w', 'layer_1.ln1_out.w', 'layer_2.ln2.w',
                 'layer_0.ln2_out.w', 'final_ln.w'):
        w = np.asarray(a[name])
        assert abs(w.mean() - 1.0) < 0.1 and 0.03 < w.std() < 0.2
    assert np.asarray(a['exit_gate.w']).shape == (64, 1)
    assert abs(float(np.asarray(a['exit_gate.b'])[0])) > 1e-3
    # a model of one pass has no gate
    assert 'exit_gate.w' not in ouro.param_shapes(dict(m, total_ut_steps=1))


def test_the_builder_refuses_by_name_what_it_does_not_build():
    m = _json(TOY_CONFIG)
    for key, value in (('hidden_act', 'gelu'), ('tie_word_embeddings', True),
                       ('rope_scaling', {'factor': 4}),
                       ('sliding_window', 128), ('use_sliding_window', True),
                       ('early_exit_threshold', 0.9)):
        with pytest.raises(ValueError, match='builds %s=' % key):
            ouro.lm_config(dict(m, **{key: value}), 32, False)
    with pytest.raises(ValueError, match='builds num_hidden_layers'):
        ouro.lm_config(dict(m, num_hidden_layers=6), 32, False)
    with pytest.raises(ValueError, match='builds num_hidden_layers'):
        ouro.lm_config(dict(m, max_window_layers=2), 32, False)
    with pytest.raises(ValueError, match='grouped K/V'):
        ouro.lm_config(dict(m, num_key_value_heads=2), 32, False)
    with pytest.raises(ValueError, match='served only'):
        ouro.lm_config(m, 32, True)
    with pytest.raises(ValueError, match='beyond the published context'):
        ouro.lm_config(m, 1024, False)


# ---- the readers ------------------------------------------------------------

def _reader(name):
    return _load(os.path.join(ROOT, 'benchmark', 'layer_metrics',
                              name + '.py'), 'reader_' + name.replace(
                                  '.', '_'))


M = _json(CONFIG)
PEAKS = {'hbm_bytes_per_s': 819e9, 'bf16_flops_per_s': 197e12}
# a window of 3 300 decode steps of 15 ms at 16 active rows of 556 live
# tokens each, the 32 cache layers booked
LIVE = 16 * 556
COUNTERS = {'kv_tokens_read_total': 3300 * LIVE * 32,
            'loop_passes_total{phase=decode}': 3300 * 4}
KERNEL = {'mosaic:paged_decode_attention_loop_pass_%d' % t: s
          for t, s in enumerate((0.84, 0.80, 0.82, 0.81))}


# ... and the 8 s of trace before it: 450 steps at 16 x 500 live tokens
TRACED = {'kv_tokens_read_total': 450 * 16 * 500 * 32}


def _traced(ops=None, **trace):
    ops = dict(KERNEL, fusion=4.0) if ops is None else ops
    return {'counters': COUNTERS, 'config': M, 'peaks': PEAKS,
            'engine_stats': {'passes': {'a_token': 4, 'traced': TRACED}},
            'window_s': 50.0, 'decode_steps': 3300,
            'active_slots_mean': 16.0, 'block_size': 32,
            'histograms': {'decode_step_seconds': (3300, 49.5)},
            'trace': dict({'window_s': 8.0, 'busy_s': 7.9,
                           'op_seconds': ops}, **trace)}


@pytest.mark.parametrize('name', NEW)
def test_a_new_reader_reads_nothing_where_there_is_nothing(name):
    """The parent commit's program (no pass in a kernel's name, no looped
    configuration), another configuration (OLMoE's: the same attention's
    shape, one pass), an untraced or a CPU run: nothing to read, nothing
    raised."""
    read = _reader(name).read
    olmoe = _json(os.path.join(ROOT, 'benchmark', 'configs',
                               'olmoe-1b-7b-0125-l6.json'))
    for facts in [{}, {'counters': {}, 'config': M},
                  {'counters': {}, 'config': {'d_model': 8}, 'trace': None},
                  dict(_traced(), trace=None, histograms={}, counters={})]:
        assert read(facts) is None
    one_pass = {'mosaic:paged_decode_attention': 3.0, 'fusion': 4.0}
    if name == 'loop_pass_step_spread':
        assert read(_traced(one_pass)) is None
        assert read(_traced({'fusion': 4.0})) is None
        return
    assert read(dict(_traced(one_pass), config=olmoe)) is None
    if name == 'paged_decode_attention_roofline.loop':
        assert read(_traced({'fusion': 4.0})) is None
        # the parent's engine, or one that no profiler session met
        for stats in ({}, {'blocks': {}}, {'passes': {'a_token': 4}},
                      {'passes': {'traced': {}}}):
            assert read(dict(_traced(), engine_stats=stats)) is None
    else:
        assert read(dict(_traced(), histograms={})) is None
        assert read(dict(_traced(), histograms={
            'decode_step_seconds': (0, 0.0)})) is None


def test_loop_weight_stream_share_on_made_up_facts():
    read = _reader('loop_weight_stream_share').read
    assert read(_traced()) == pytest.approx(
        100.0 * (flops_ouro.loop_weight_stream_bytes(M) / 819e9) / 0.015)
    # the issue's step: 8.5 ms of a 15 ms step
    assert read(_traced()) == pytest.approx(56.8, abs=0.5)
    assert read(dict(_traced(), trace=None)) == read(_traced())


def test_attention_kv_step_share_loop_on_made_up_facts():
    read = _reader('attention_kv_step_share.loop').read
    assert read(_traced()) == pytest.approx(
        100.0 * (LIVE * 524288 / 819e9) / 0.015)
    assert read(_traced()) == pytest.approx(38.0, abs=0.5)
    # with the weights' share: what the step's bytes take of the step
    both = read(_traced()) + _reader('loop_weight_stream_share').read(
        _traced())
    assert 90 < both < 100
    assert read(dict(_traced(), decode_steps=0)) is None


def test_paged_decode_attention_roofline_loop_on_made_up_facts():
    read = _reader('paged_decode_attention_roofline.loop').read
    need = TRACED['kv_tokens_read_total'] * 16384
    kernel = sum(KERNEL.values())
    assert read(_traced()) == pytest.approx(100.0 * need / kernel / 819e9)
    assert 50 < read(_traced()) < 100
    # a stall of the machine's host inside the trace (its window 3 s longer
    # than the device was busy) moves nothing, nor does a window whose
    # contexts are longer than the trace's: both sides are the trace's own.
    # The accepted reader's division -- the WINDOW's bytes a second by the
    # kernel's share of the trace's window -- reads 11 / 8 x 556 / 500 of
    # this there, over 105 %
    stalled = _traced(window_s=11.0)
    assert read(stalled) == read(_traced()) < 100
    assert read(dict(_traced(), counters={}, window_s=None)) == \
        read(_traced())
    accepted = 100.0 * (3300 * LIVE * 32 * 16384 / 50.0 / 819e9) \
        / (kernel / 11.0)
    assert accepted > 105
    # a one-pass program's kernel has the name without a pass
    assert read(_traced({'mosaic:paged_decode_attention': kernel})) == \
        read(_traced())


def test_loop_pass_step_spread_on_made_up_facts():
    read = _reader('loop_pass_step_spread').read
    assert read(_traced()) == pytest.approx(0.84 / 0.80)
    even = {k: 0.8 for k in KERNEL}
    assert read(_traced(even)) == 1.0
    # numbered copies of an instruction are summed under its name by
    # reduce_trace.op_name before the reader sees them
    from benchmark import reduce_trace
    assert reduce_trace.op_name(
        '%paged_decode_attention_loop_pass_2.7 = f32[16,1,2048] '
        'custom-call(), custom_call_target="tpu_custom_call"') == \
        'mosaic:paged_decode_attention_loop_pass_2'


# ---- the comparison script, as the chip runs it -----------------------------

def test_ouro_control_main_at_toy_width(capsys):
    from benchmark.reference import ouro_control
    rc = ouro_control.main([TOY_CONFIG, TOY_TRAFFIC, '5', '3000000009'])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith('{')]
    assert rc == 0 and len(lines) == 4
    for out in lines:
        assert out['rows'] == min(25, 40 - out['prompt_len'] + 1)
        assert out['logits_vs_ref'][0] < ouro_control.LOGITS_RMS_LIMIT
        assert out['refused_by'] == []
        assert out['greedy_margin_worst'] == 0.0
        # on the CPU the default precision IS float32: the programs built
        # without the configuration's read what the served ones do
        lower = out['controls'].pop('default-matmul-precision')
        assert lower['refused_by'] == []
        assert set(out['controls']) == {'bfloat16', 'crossed-cache',
                                        'three-passes'}
        for name, reading in out['controls'].items():
            assert 'logits' in reading['refused_by'], name
            assert reading['greedy_margin_check_rows'] <= \
                reading['greedy_margin_worst']
