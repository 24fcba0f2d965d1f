"""The NVIDIA-Nemotron-3-Nano-30B-A3B configuration's benchmark files (ISSUE
48): a toy cell with the new builder through run.py end to end on the CPU
(its own toy manifest; prompts chunked over the widest bucket resume from
the slot's state row), the manifest's entries and the published file
against the catalog's row, the cell's traffic (Jamba2's file, unedited),
flops_nemotron's formulae against a count of param_shapes and against the
issue's table, the three new readers and the accepted readers the cell is
listed under on made-up facts of this configuration (no roofline over 100
on a trace that spends its whole window in the kernel at peak), and the
comparison script's main() at toy width."""
import json
import os

import numpy as np
import pytest

from benchmark import flops_nemotron
from benchmark.models import nemotron

from test_bench_olmoe import _last_json, _load, run_on_cpu   # noqa: F401
from test_bench_run import MANIFEST, by_name, check_config_entry

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TOY_MANIFEST = os.path.join(HERE, 'fixtures', 'BENCHMARK.toy.nemotron.json')
TOY_CONFIG = os.path.join(HERE, 'configs', 'toy-nemotron.json')
NAME = 'nemotron-3-nano-30b-a3b-ep8-l20'
CONFIG = os.path.join(ROOT, 'benchmark', 'configs', NAME + '.json')
CELL = 'nemotron3-serve-reason128'
CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'
NEW = ('ssd_decode_state_roofline', 'ssd_state_step_share',
       'nemotron_moe_ffn_roofline')
ROW = (64 * 64 * 128 + 3 * 6144) * 4   # one layer's state and tail, a slot
REDUCED = {'num_hidden_layers': 52, 'n_routed_experts': 128,
           'vocab_size': 131072}


def _json(path):
    with open(path) as f:
        return json.load(f)


# ---- the toy cell through run.py --------------------------------------------

def test_end_to_end_line(run_on_cpu, capsys):              # noqa: F811
    rc = run_on_cpu.main(['--workload', 'toy-serve-nemotron', '--seed',
                          '3000000001', '--seconds', '0.5', '--trace', '0'],
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
    rc = run_on_cpu.main(['--workload', 'toy-serve-nemotron', '--seed', '7',
                          '--seconds', '0.7', '--trace', '1'],
                         manifest_path=TOY_MANIFEST)
    out, lines = _last_json(capsys)
    assert rc == 0 and out['correct'] is True
    # on the CPU no operation of the trace is a Mosaic kernel and
    # peak_hbm_gb.serve has nothing to read: the two rooflines and the
    # peak return nothing
    assert set(out['metrics']) == {
        'decode_step_ms', 'decode_hbm_share', 'device_idle_share.serve',
        'ssd_state_step_share', 'moe_held_assignment_share'}
    assert 0 < out['metrics']['ssd_state_step_share']['value'] < 100
    assert 0 < out['metrics']['decode_hbm_share']['value'] < 100
    # experts 2..5 of 8 held, 3 a token
    assert 20 < out['metrics']['moe_held_assignment_share']['value'] < 80
    facts = json.loads([ln for ln in lines
                        if ln.startswith('facts: ')][-1][len('facts: '):])
    assert facts['decode_bytes_per_step'] > 0


# ---- the manifest and the published file ------------------------------------

def check_nemotron_entry(manifest):
    """The configuration, its cell and the metrics that list the cell,
    each found by name: where they stand in their lists is
    test_bench_manifest.py's to hold (appended, never put in)."""
    conf = by_name(manifest['configs'], NAME)
    check_config_entry(conf, manifest)
    assert conf['reduced'] == ['num_hidden_layers', 'hybrid_override_pattern',
                               'n_routed_experts', 'vocab_size']
    assert conf['source'] == 'https://huggingface.co/nvidia/' \
        'NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json'
    cell = by_name(manifest['workloads'], CELL)
    assert cell == dict(cell, name=CELL, config=conf['name'],
                        traffic='reason128-closed', chips=1)
    listed = {x['name'] for x in manifest['end_to_end']
              + manifest['per_layer'] if CELL in x.get('workloads', ())}
    # `itl_p95_ms`, every per-layer metric that moves it and whose reader
    # asks nothing of the configuration that it lacks, and its own three
    # (K-EXAONE's cell's list, for K-EXAONE's reason). At least: a later PR
    # may append
    assert listed >= {
        'itl_p95_ms', 'decode_step_ms', 'decode_host_gap_ms',
        'decode_host_gap_ms.admit', 'decode_host_gap_ms.feed',
        'decode_host_gap_ms.dispatch', 'decode_host_gap_ms.deliver',
        'decode_sampled_step_share', 'server_loop_unaccounted_share',
        'admission_ms', 'admission_ms.dispatch', 'admission_ms.drain',
        'admission_ms.fetch', 'token_gap_ms.admission', 'token_gap_ms.plain',
        'token_gap_admission_share'} | set(NEW)
    # NOT `serve_tokens_per_s`, nor a metric that moves it: six seeds on
    # the chip read it 8.8 % apart where 2.5 % admits a cell (two of the
    # six runs lost 7 and 11 % of their tokens at the other runs'
    # `itl_p95_ms`; the same seed again read the others' rate: PERF.md
    # section 6), and a per-layer metric lists the cells that report what
    # it moves
    moved = {x['name']: x.get('moves') for x in manifest['per_layer']}
    assert 'serve_tokens_per_s' not in listed
    assert not {n for n in listed if moved.get(n) == 'serve_tokens_per_s'}
    # NOT under Jamba2's three readers (keyed to mamba_d_state /
    # mamba_expand, which this row does not have), not under another
    # family's kernel, and not under paged_decode_attention_roofline:
    # test_bench_lfm2.py holds its list with `==`
    assert not {n for n in listed if n.startswith(('ssm_', 'mla_', 'kv_'))}
    assert not listed & {'paged_decode_attention_roofline',
                         'window_decode_attention_roofline',
                         'lfm2_moe_ffn_roofline', 'kexaone_moe_ffn_roofline',
                         'prefix_hit_token_share', 'ttft_p95_ms'}
    by = {x['name']: x for x in manifest['per_layer']}
    for name in NEW:
        assert by[name]['workloads'] == [CELL] or CELL in by[name][
            'workloads']
    assert {by[n]['moves'] for n in NEW} == {'itl_p95_ms'}
    # NOT a roofline of the prefill's scan: its rows would be the window's
    # and its time the trace's, which in this cell begins before the
    # admissions reach their steady rate (PERF.md section 7)
    assert 'ssd_prefill_scan_roofline' not in by


def test_config_entry_admits_the_new_entry():
    check_nemotron_entry(MANIFEST)


def test_the_cells_traffic_is_jamba2s_file():
    """The cell REUSES benchmark/traffic/reason128-closed.json as it lies
    (test_bench_jamba.py holds it letter for letter): the two cells differ
    in the model alone."""
    cell = by_name(MANIFEST['workloads'], CELL)
    jamba = by_name(MANIFEST['workloads'], 'jamba2-serve-reason128')
    assert cell['traffic'] == jamba['traffic'] == 'reason128-closed'
    tr = _json(os.path.join(ROOT, 'benchmark', 'traffic',
                            cell['traffic'] + '.json'))
    assert tr['engine'] == {'paged': True, 'slots': 128, 'block_size': 32,
                            'max_len': 3072,
                            'prompt_buckets': [128, 256, 512],
                            'num_blocks': 8192}
    # the check's longest prompt is two prompt chunks and eight SSD blocks
    m = _json(CONFIG)
    assert tr['prompt_len']['max'] == 2 * 512 == 8 * m['chunk_size']
    assert not os.path.exists(os.path.join(ROOT, 'benchmark', 'traffic',
                                           'reason96-closed.json'))


def test_the_published_file_keeps_every_number_of_the_catalogs_row():
    if not os.path.isfile(CATALOG):
        pytest.skip('no catalog on this machine')
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f)
                if r['name'] == 'NVIDIA-Nemotron-3-Nano-30B-A3B-BF16']
    m = _json(CONFIG)
    changed = {k for k, v in row['config'].items() if m.get(k, '?') != v}
    assert changed == set(m['reduced']) == set(REDUCED) | {
        'hybrid_override_pattern'}
    assert {k: m['reduced_from'][k] for k in REDUCED} == REDUCED == {
        k: row['config'][k] for k in REDUCED}
    full = row['config']['hybrid_override_pattern']
    assert m['reduced_from']['hybrid_override_pattern'] == full
    assert m['hybrid_override_pattern'] == full[:20] \
        == 'MEMEM*EMEMEM*EMEMEM*'
    assert (full.count('M'), full.count('E'), full.count('*'), len(full)) \
        == (23, 23, 6, 52)
    assert m['source'].startswith(row['source_url'])
    assert m['builder'] == 'nemotron' and m['first_expert_held'] == 0
    # every width of the row
    assert (m['hidden_size'], m['mamba_num_heads'], m['mamba_head_dim'],
            m['n_groups'], m['ssm_state_size'], m['conv_kernel'],
            m['chunk_size'], m['use_conv_bias'], m['num_attention_heads'],
            m['num_key_value_heads'], m['head_dim'],
            m['num_experts_per_tok'], m['moe_intermediate_size'],
            m['moe_shared_expert_intermediate_size'], m['n_shared_experts'],
            m['routed_scaling_factor'], m['norm_topk_prob'],
            m['mlp_hidden_act'], m['layer_norm_epsilon'],
            m['tie_word_embeddings']) == \
        (2688, 64, 64, 8, 128, 4, 128, True, 32, 2, 128, 6, 1856, 3712, 1,
         2.5, True, 'relu2', 1e-5, False)
    assert m['deployment'].strip() and m['changed']
    assert set(m['assumed']) == {'no_rotation', 'd_inner', 'time_step_limit',
                                 'n_group_topk_group', 'chunk_size',
                                 'norm_eps'}
    assert 'float32' in m['changed']['serving_dtype']
    assert "'highest'" in m['changed']['matmul_precision']
    assert '3072' in m['changed']['context']


# ---- flops_nemotron against a count of the parameters -----------------------

def _count(shapes, pick=lambda name: True):
    return sum(int(np.prod(s)) for n, s in shapes.items() if pick(n))


@pytest.mark.parametrize('path', [CONFIG, TOY_CONFIG], ids=[NAME, 'toy'])
def test_flops_nemotron_counts_what_param_shapes_lists(path):
    m = _json(path)
    f = flops_nemotron
    shapes = nemotron.param_shapes(m)
    assert f.param_count(m) == _count(shapes)
    for i, letter in enumerate(f.pattern(m)):
        assert f.layer_param_count(m, letter) == _count(
            shapes, lambda k: k.startswith('layer_%d.' % i))
    cfg = nemotron.lm_config(m, 32, False)
    assert f.kv_bytes_per_token(m) == 2 * cfg.n_attn_layers * cfg.kv_width * 4
    from paddle_tpu.models import transformer as T
    pools = T.kv_cache_shapes(cfg, 4, 8, 1)
    # one slot's row of the state pool, and the K - 1 rows that count of
    # the 8 its block of the tail pool holds, are `state_bytes_per_slot`
    assert pools[T.SSD_TAIL][2] == 8
    assert f.state_bytes_per_slot(m) == 4 * (
        int(np.prod(pools[T.SSD_STATE][1:]))
        + int(np.prod(pools[T.SSD_TAIL][1:])) * (m['conv_kernel'] - 1) // 8)
    one = f.decode_bytes_per_step(m, 0, 1)
    assert one == 4 * _count(shapes) + 2 * f.state_bytes_per_slot(m)
    assert f.decode_bytes_per_step(m, 100, 1) - one == \
        100 * f.kv_bytes_per_token(m)
    # two matrices an expert, the gathered row, up, its square, the result
    d, w = m['hidden_size'], m['moe_intermediate_size']
    assert f.grouped_matmul_bytes(m, 3, 10) == 4 * (3 * 2 * d * w
                                                    + 10 * (2 * d + 2 * w))


def test_the_published_configuration_is_what_the_issue_sized():
    m = _json(CONFIG)
    f = flops_nemotron
    assert f.param_count(m) == 1946570560
    assert f.layer_param_count(m, 'M') == 38744896
    assert f.layer_param_count(m, '*') == 23399040
    assert f.layer_param_count(m, 'E', 0) == 20302592
    assert f.expert_param_count(m) == 9977856
    assert (f.n_layers(m, 'M'), f.n_layers(m, 'E'), f.n_layers(m, '*')) \
        == (9, 8, 3)
    # the whole model, as published: 31.58 B
    whole = dict(m, **m['reduced_from'])
    whole['n_routed_experts'] = 128
    assert f.param_count(whole) == 31577940288
    assert 4 * f.param_count(m) == pytest.approx(7.786e9, rel=1e-3)
    # the state: 19.54 MB a slot whatever the context
    assert f.state_row_bytes(m) == ROW == 2170880
    assert f.state_bytes_per_slot(m) == 19537920
    assert f.kv_bytes_per_token(m) == 6144
    assert 32 * f.kv_bytes_per_token(m) == 196608
    # ~13.3 GB a step at 128 rows and ~75 k live tokens, the state ~38 %
    step = f.decode_bytes_per_step(m, 75000, 128)
    assert step == pytest.approx(13.25e9, rel=2e-3)
    assert 2 * 128 * f.state_bytes_per_slot(m) / step == pytest.approx(
        0.378, abs=0.003)
    cfg = nemotron.lm_config(m, 3072, False)
    from paddle_tpu.models import transformer as T
    assert T.kv_cache_shapes(cfg, 8192, 32, 128) == {
        'gen_kv_k': (8192, 3, 32, 256), 'gen_kv_v': (8192, 3, 32, 256),
        'gen_ssd_state': (129, 9, 128, 4096),
        'gen_ssd_tail': (129, 9, 8, 6144)}
    assert (cfg.n_head, cfg.n_kv_head, cfg.head_dim, cfg.kv_width,
            cfg.attn_width) == (32, 2, 128, 256, 4096)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state,
            cfg.ssm_conv, cfg.ssm_chunk, cfg.ssd_inner, cfg.ssd_conv_width) \
        == (64, 64, 8, 128, 4, 128, 4096, 6144)
    assert ''.join({'ssd': 'M', 'ffn': 'E', 'attention': '*'}[k]
                   for k in cfg.layer_types) == 'MEMEM*EMEMEM*EMEMEM*'
    assert (cfg.matmul_precision, cfg.position, cfg.ffn, cfg.expert_form,
            cfg.norm, cfg.rms_eps, cfg.bias, cfg.tie_embeddings,
            cfg.qk_norm) == \
        ('highest', 'none', 'moe', 'relu2', 'rms_norm', 1e-5, False, False,
         False)
    assert [cfg.has_mixer(i) for i in range(3)] == [True, False, True]
    assert [cfg.has_ffn(i) for i in range(3)] == [False, True, False]
    assert (cfg.n_experts, cfg.experts_per_token, cfg.experts_held,
            cfg.expert_width, cfg.shared_expert_width, cfg.moe_score,
            cfg.routed_scale, cfg.norm_topk_prob, cfg.n_moe_layers) == \
        (128, 6, (0, 16), 1856, 3712, 'sigmoid', 2.5, True, 8)
    assert not any(cfg.rotates(i) for i in range(20))


def test_init_params_is_seeded_and_takes_mamba2s_initialisation():
    m = _json(TOY_CONFIG)
    a = nemotron.init_params(m, 3000000001)
    b = nemotron.init_params(m, 3000000001)
    c = nemotron.init_params(m, 5)
    assert sorted(a) == sorted(nemotron.param_shapes(m))
    for name, shape in nemotron.param_shapes(m).items():
        assert tuple(a[name].shape) == tuple(shape)
        np.testing.assert_array_equal(np.asarray(a[name]),
                                      np.asarray(b[name]))
    assert np.abs(np.asarray(a['tok_emb.w'])
                  - np.asarray(c['tok_emb.w'])).max() > 0
    ln = np.asarray(a['layer_0.ssd.norm.w'])
    assert abs(ln.mean() - 1.0) < 0.05 and 0.05 < ln.std() < 0.2
    assert np.asarray(a['layer_3.attn.qkv.w']).std() == pytest.approx(
        0.02, rel=0.2)
    assert 0.2 < np.asarray(a['layer_0.ssd.conv.w']).std() < 0.4
    assert np.abs(np.asarray(a['layer_1.moe.router.bias'])).max() < 0.05
    # the recurrence: A = -(1 .. 16) a head, D = 1, the step between
    # time_step_min and time_step_max at a zero input
    decay = np.exp(np.asarray(a['layer_2.ssd.A_log'], 'float64'))
    assert 1.0 <= decay.min() and decay.max() <= 16.0 and decay.std() > 1
    np.testing.assert_array_equal(np.asarray(a['layer_2.ssd.D']), 1.0)
    dt = np.logaddexp(0, np.asarray(a['layer_2.ssd.dt.b'], 'float64'))
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 1e-1 * 1.001


# ---- the readers ------------------------------------------------------------

def _reader(name):
    return _load(os.path.join(ROOT, 'benchmark', 'layer_metrics',
                              name + '.py'), 'reader_' + name.replace(
                                  '.', '_'))


M = _json(CONFIG)
PEAKS = {'hbm_bytes_per_s': 819e9, 'bf16_flops_per_s': 197e12}
# a window of 200 decode steps of 9 Mamba-2 and 8 expert layers at 120
# active rows; 30 admissions of which 5 ran as two chunks, 9 000 real
# prompt rows; every held expert touched at every step and prefill
COUNTERS = {'ssd_state_rows_updated_total': 200 * 120 * 9,
            'ssd_prefill_rows_total': 9000 * 9,
            'ssd_state_resumes_total': 5,
            'kv_tokens_read_total': 200 * 120 * 600 * 3,
            'moe_experts_touched_total': 230 * 8 * 16,
            'moe_assignments_total': (200 * 120 + 9000) * 8 * 6,
            'moe_held_assignments_total': (200 * 120 + 9000) * 6}
HIST = {'prefill_seconds': (30, 1.2), 'decode_step_seconds': (200, 5.0)}
ROOFLINES = {'ssd_decode_state_roofline': 'mosaic:ssd_decode_update',
             'nemotron_moe_ffn_roofline': 'mosaic:ragged-dot'}


def _traced(**ops):
    return {'counters': COUNTERS, 'config': M, 'peaks': PEAKS,
            'histograms': HIST, 'window_s': 5.0, 'decode_steps': 200,
            'decode_bytes_per_step': flops_nemotron.decode_bytes_per_step(
                M, 72000, 120),
            'trace': {'window_s': 2.0, 'busy_s': 1.9, 'op_seconds': ops}}


@pytest.mark.parametrize('name', NEW)
def test_a_new_reader_reads_nothing_where_there_is_nothing(name):
    """The parent commit's program (no such counter, no such operation),
    another configuration (Jamba2's under the same traffic among them), an
    untraced or a CPU run: nothing to read, nothing raised."""
    read = _reader(name).read
    others = [_json(os.path.join(ROOT, 'benchmark', 'configs', n + '.json'))
              for n in ('ai21-jamba2-3b', 'k-exaone-236b-a23b-ep16-l5')]
    every = {op: 0.2 for op in ROOFLINES.values()}
    for facts in [{}, {'counters': {}, 'config': M},
                  {'counters': {}, 'config': {'d_model': 8}, 'trace': None},
                  dict(_traced(**every), config={'hidden_size': 8}),
                  dict(_traced(**every), counters={}),
                  dict(_traced(**every), counters={
                      'kv_tokens_read_total': 5})] + [
            dict(_traced(**every), config=m) for m in others]:
        assert read(facts) is None
    if name in ROOFLINES:
        assert read(_traced(fusion=0.5)) is None    # no such operation
        assert read(dict(_traced(**every), trace=None)) is None
        # another kernel is not this one
        assert read(_traced(**{op: 0.2 for n, op in ROOFLINES.items()
                               if n != name})) is None
    else:
        assert read(dict(_traced(), decode_bytes_per_step=None)) is None


def test_ssd_state_step_share_on_made_up_facts():
    read = _reader('ssd_state_step_share').read
    need = flops_nemotron.decode_bytes_per_step(M, 72000, 120)
    assert read(_traced()) == pytest.approx(
        100.0 * 2 * 120 * 19537920 / need)
    # 128 rows and the issue's ~75 k live tokens: ~38 %
    full = dict(_traced(), counters={
        'ssd_state_rows_updated_total': 200 * 128 * 9},
        decode_bytes_per_step=flops_nemotron.decode_bytes_per_step(
            M, 75000, 128))
    assert read(full) == pytest.approx(37.8, abs=0.3)


def test_ssd_decode_state_roofline_on_made_up_facts():
    read = _reader('ssd_decode_state_roofline').read
    need = 2 * 200 * 120 * 9 * ROW
    facts = _traced(**{'mosaic:ssd_decode_update': 0.5,
                       'mosaic:ssm_decode_conv': 0.05,
                       'mosaic:ssd_prefill_scan': 0.2,
                       'mosaic:paged_decode_attention': 0.1, 'fusion': 0.9})
    # the bytes need need / 5 s / peak of every second; the two kernels
    # run in 0.55 / 2 of every second
    assert read(facts) == pytest.approx(
        100.0 * (need / 5.0 / 819e9) / (0.55 / 2.0))
    assert 0 < read(facts) < 100.0
    assert flops_nemotron.ssd_decode_state_bytes(M, 1) == 2 * ROW


def test_nemotron_moe_ffn_roofline_on_made_up_facts():
    read = _reader('nemotron_moe_ffn_roofline').read
    need = flops_nemotron.grouped_matmul_bytes(
        M, COUNTERS['moe_experts_touched_total'],
        COUNTERS['moe_held_assignments_total'])
    facts = _traced(**{'mosaic:ragged-dot': 0.5, 'mosaic:ragged-dot.1': 0.3,
                       'fusion': 0.4})
    assert read(facts) == pytest.approx(
        100.0 * (need / 5.0 / 819e9) / (0.8 / 2.0))
    assert 0 < read(facts) < 100.0
    # kexaone's reader asks for its own family's keys and reads nothing
    assert _reader('kexaone_moe_ffn_roofline').read(facts) is None


def test_the_accepted_readers_the_cell_lists_read_this_configuration():
    """`decode_hbm_share` divides this configuration's
    `decode_bytes_per_step` by the step's time and stays under 100 at the
    chip's peak; `moe_held_assignment_share` divides two counters (16 of
    128 held: 12.5 % under even routing); Jamba2's three readers, keyed to
    its family, read nothing here."""
    facts = _traced()
    need = facts['decode_bytes_per_step']
    at_peak = dict(facts, histograms={
        'decode_step_seconds': (200, 200 * need / 819e9)})
    assert _reader('decode_hbm_share').read(at_peak) == pytest.approx(100.0)
    assert 0 < _reader('decode_hbm_share').read(facts) < 100.0
    assert _reader('decode_step_ms').read(facts) == pytest.approx(25.0)
    assert _reader('moe_held_assignment_share').read(facts) == \
        pytest.approx(12.5)
    every = _traced(**{'mosaic:ssm_decode_update': 0.2,
                       'mosaic:ssm_decode_conv': 0.2,
                       'mosaic:ssm_prefill_scan': 0.2})
    for name in ('ssm_decode_state_roofline', 'ssm_prefill_scan_roofline',
                 'ssm_state_step_share', 'kv_window_read_share'):
        assert _reader(name).read(every) is None


@pytest.mark.parametrize('name,counters', [
    ('ssd_decode_state_roofline',
     lambda s: {'ssd_state_rows_updated_total': int(819e9 * s / (2 * ROW))}),
    ('nemotron_moe_ffn_roofline',
     lambda s: {'moe_experts_touched_total': int(
         819e9 * s / (4 * 9977856))})])
def test_a_roofline_never_passes_100_at_peak(name, counters):
    """A made-up trace that spends its WHOLE window in the kernel, at the
    chip's peak all the while: exactly the roofline, and less for any time
    beside it."""
    read, op = _reader(name).read, ROOFLINES[name]
    facts = {'counters': counters(4.0), 'config': M, 'peaks': PEAKS,
             'window_s': 4.0, 'trace': {'window_s': 2.0, 'busy_s': 2.0,
                                        'op_seconds': {op: 2.0}}}
    assert 99.9 < read(facts) <= 100.0
    facts['trace']['op_seconds'] = {op: 1.0}       # twice peak: impossible
    assert read(facts) > 105.0                     # and it shows, unclipped


# ---- the comparison script, as the chip runs it -----------------------------

def test_nemotron_control_main_at_toy_width(capsys):
    from benchmark.reference import nemotron_control
    rc = nemotron_control.main([
        TOY_CONFIG, os.path.join(HERE, 'traffic', 'toy-serve-nemotron.json'),
        '5', '3000000009'])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith('{')]
    assert rc == 0 and len(lines) == 4
    always = {'stale-state', 'group-0', 'norm-all-channels',
              'norm-before-gate', 'no-D', 'no-conv-bias', 'relu', 'silu',
              'gated-experts', 'no-shared-expert', 'no-routed-scale',
              'bias-in-weights', 'rope-on-attention', 'bfloat16',
              'bfloat16-state', 'default-matmul-precision'}
    for out in lines:
        n = out['prompt_len']
        last = n - (n - 1) // 16 * 16
        want = set(always)
        assert out['rows'] == min(25, 72 - n + 1)
        # the same row of the pools served every prompt: no reading shows
        # the one before
        assert out['logits_vs_ref'][1] < 1e-5
        assert out['refused_by'] == []
        assert out['greedy_margin_worst'] == 0.0
        # on the CPU the default precision IS float32: the programs built
        # without the configuration's read what the served ones do
        lower = out['controls'].pop('default-matmul-precision')
        assert lower['logits_vs_ref'][1] < 1e-5 and lower['refused_by'] == []
        want.discard('default-matmul-precision')
        if n > 16:
            want.add('chunk-edge')
        if n > 8:
            want.add('block-edge')
        if last not in (8, 16):
            want.add('pad-rows')
        assert set(out['controls']) == want
        for name, reading in out['controls'].items():
            floor = 1e-6 if name == 'bfloat16-state' else 2e-5
            assert reading['logits_vs_ref'][1] > floor, name
            assert set(reading['refused_by']) <= {'logits', 'tokens'}
            assert reading['greedy_margin_check_rows'] <= \
                reading['greedy_margin_worst']
