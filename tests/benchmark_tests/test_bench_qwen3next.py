"""The Qwen3-Next-80B-A3B-Instruct configuration's benchmark files (ISSUE
55): a toy cell with the new builder through run.py end to end on the CPU
(its own toy manifest; prompts chunked over the widest bucket resume from
the slot's state row), the manifest's entries and the published file
against the catalog's row, the cell's traffic, flops_qwen3next's formulae
against a count of param_shapes and against the issue's table, the four new
readers and the accepted readers the cell is listed under on made-up facts
of this configuration (no roofline over 100 on a trace that spends its
whole window in the kernel at peak), and the comparison script's main() at
toy width."""
import json
import os

import numpy as np
import pytest

from benchmark import flops_qwen3next
from benchmark.models import qwen3next

from test_bench_olmoe import _last_json, _load, run_on_cpu   # noqa: F401
from test_bench_run import MANIFEST, by_name, check_config_entry

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TOY_MANIFEST = os.path.join(HERE, 'fixtures', 'BENCHMARK.toy.qwen3next.json')
TOY_CONFIG = os.path.join(HERE, 'configs', 'toy-qwen3next.json')
NAME = 'qwen3-next-80b-a3b-ep8-l8'
CONFIG = os.path.join(ROOT, 'benchmark', 'configs', NAME + '.json')
CELL = 'qwen3next-serve-longmix64'
CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'
NEW = ('gdn_decode_state_roofline', 'gdn_state_step_share',
       'qwen3next_moe_ffn_roofline', 'gdn_prefill_chunk_roofline')
ROW = (32 * 128 * 128 + 3 * 8192) * 4   # one layer's state and tail, a slot
REDUCED = {'num_hidden_layers': 48, 'num_experts': 512,
           'vocab_size': 151936}


def _json(path):
    with open(path) as f:
        return json.load(f)


# ---- the toy cell through run.py --------------------------------------------

def test_end_to_end_line(run_on_cpu, capsys):              # noqa: F811
    rc = run_on_cpu.main(['--workload', 'toy-serve-qwen3next', '--seed',
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
    rc = run_on_cpu.main(['--workload', 'toy-serve-qwen3next', '--seed', '7',
                          '--seconds', '0.7', '--trace', '1'],
                         manifest_path=TOY_MANIFEST)
    out, lines = _last_json(capsys)
    assert rc == 0 and out['correct'] is True
    # on the CPU no operation of the trace is a Mosaic kernel and
    # peak_hbm_gb.serve has nothing to read: the three rooflines and the
    # peak return nothing
    assert set(out['metrics']) == {
        'decode_step_ms', 'decode_hbm_share', 'device_idle_share.serve',
        'gdn_state_step_share', 'moe_held_assignment_share'}
    assert 0 < out['metrics']['gdn_state_step_share']['value'] < 100
    assert 0 < out['metrics']['decode_hbm_share']['value'] < 100
    # experts 4..7 of 16 held, 3 a token
    assert 5 < out['metrics']['moe_held_assignment_share']['value'] < 60
    facts = json.loads([ln for ln in lines
                        if ln.startswith('facts: ')][-1][len('facts: '):])
    assert facts['decode_bytes_per_step'] > 0


# ---- the manifest and the published file ------------------------------------

def check_qwen3next_entry(manifest):
    """The configuration, its cell and the metrics that list the cell,
    each found by name: where they stand in their lists is
    test_bench_manifest.py's to hold (appended, never put in)."""
    conf = by_name(manifest['configs'], NAME)
    check_config_entry(conf, manifest)
    assert conf['reduced'] == ['num_hidden_layers', 'num_experts',
                               'vocab_size']
    assert conf['source'] == 'https://huggingface.co/Qwen/' \
        'Qwen3-Next-80B-A3B-Instruct/blob/main/config.json'
    cell = by_name(manifest['workloads'], CELL)
    assert cell == dict(cell, name=CELL, config=conf['name'],
                        traffic='longmix64-closed', chips=1)
    listed = {x['name'] for x in manifest['end_to_end']
              + manifest['per_layer'] if CELL in x.get('workloads', ())}
    # `itl_p95_ms`, every per-layer metric that moves it or `setup_s` and
    # whose reader asks nothing of the configuration that it lacks
    # (K-EXAONE's cell's list and the nine `setup_program_s*`, as Mellum
    # 2's cell has them, with the straggler expert), and its own four. At
    # least: a later PR may append
    assert listed >= {
        'itl_p95_ms', 'decode_step_ms', 'decode_host_gap_ms',
        'decode_host_gap_ms.admit', 'decode_host_gap_ms.feed',
        'decode_host_gap_ms.dispatch', 'decode_host_gap_ms.deliver',
        'decode_sampled_step_share', 'server_loop_unaccounted_share',
        'admission_ms', 'admission_ms.dispatch', 'admission_ms.drain',
        'admission_ms.fetch', 'token_gap_ms.admission', 'token_gap_ms.plain',
        'token_gap_admission_share', 'moe_load_max_over_mean',
        'setup_program_s', 'setup_program_s.import', 'setup_program_s.build',
        'setup_program_s.trace', 'setup_program_s.lower',
        'setup_program_s.compile', 'setup_program_s.cache_load',
        'setup_program_s.place', 'setup_program_s.first_run'} | set(NEW)
    # NOT `serve_tokens_per_s`, nor a metric that moves it (PERF.md section
    # 6 has the six seeds' spread), and a per-layer metric lists the cells
    # that report what it moves
    moved = {x['name']: x.get('moves') for x in manifest['per_layer']}
    assert 'serve_tokens_per_s' not in listed
    assert not {n for n in listed if moved.get(n) == 'serve_tokens_per_s'}
    # NOT under another family's readers, and not under
    # paged_decode_attention_roofline: test_bench_lfm2.py holds its list
    # with `==` (PERF.md section 7 notes the cell for M7's benchmark PR)
    assert not {n for n in listed
                if n.startswith(('ssm_', 'ssd_', 'mla_', 'kv_'))}
    assert not listed & {'paged_decode_attention_roofline',
                         'window_decode_attention_roofline',
                         'lfm2_moe_ffn_roofline', 'kexaone_moe_ffn_roofline',
                         'nemotron_moe_ffn_roofline',
                         'mellum2_moe_ffn_roofline',
                         'prefix_hit_token_share', 'ttft_p95_ms'}
    by = {x['name']: x for x in manifest['per_layer']}
    for name in NEW:
        assert CELL in by[name]['workloads']
        assert by[name]['unit'] == '%'
    assert {by[n]['moves'] for n in NEW} == {'itl_p95_ms'}
    assert {by[n]['layer'] for n in NEW} == {'kernels', 'model step'}


def test_config_entry_admits_the_new_entry():
    check_qwen3next_entry(MANIFEST)


def test_the_cells_traffic_is_the_issues():
    tr = _json(os.path.join(ROOT, 'benchmark', 'traffic',
                            'longmix64-closed.json'))
    assert tr['kind'] == 'serve' and tr['sampling'] == 'greedy'
    assert tr['arrival'] == {'kind': 'closed', 'clients': 64,
                             'stagger_s': 0.1}
    assert tr['prompt_len'] == {'dist': 'lognormal', 'median': 2048,
                                'sigma': 0.8, 'min': 256, 'max': 8192}
    assert tr['output_len'] == {'dist': 'lognormal', 'median': 512,
                                'sigma': 0.5, 'min': 128, 'max': 1024}
    assert (tr['pool_size'], tr['shared_prefix_len'], tr['group_size']) \
        == (512, 0, 0)
    assert tr['engine'] == {'paged': True, 'slots': 64, 'block_size': 32,
                            'max_len': 9216,
                            'prompt_buckets': [128, 256, 512],
                            'num_blocks': 18432}
    assert (tr['check_new_tokens'], tr['trace_seconds']) == (8, 8.0)
    # every slot can hold the longest request: no admission waits for blocks
    e = tr['engine']
    assert e['num_blocks'] == e['slots'] * e['max_len'] // e['block_size']
    assert tr['prompt_len']['max'] + tr['output_len']['max'] <= e['max_len']
    # the check's longest prompt is sixteen prompt chunks, each eight blocks
    # of the delta rule
    m = _json(CONFIG)
    assert tr['prompt_len']['max'] == 16 * 512 == 16 * 8 * m['gdn_chunk']


def test_the_published_file_keeps_every_number_of_the_catalogs_row():
    if not os.path.isfile(CATALOG):
        pytest.skip('no catalog on this machine')
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f)
                if r['name'] == 'Qwen3-Next-80B-A3B-Instruct']
    m = _json(CONFIG)
    changed = {k for k, v in row['config'].items() if m.get(k, '?') != v}
    assert changed == set(m['reduced']) == set(REDUCED)
    assert m['reduced_from'] == REDUCED == {
        k: row['config'][k] for k in REDUCED}
    assert (m['num_hidden_layers'], m['num_experts'], m['vocab_size']) \
        == (8, 64, 18992)
    assert m['vocab_size'] * 8 == REDUCED['vocab_size']
    assert m['source'].startswith(row['source_url'])
    assert m['builder'] == 'qwen3next' and m['first_expert_held'] == 0
    # every width of the row
    assert (m['hidden_size'], m['num_attention_heads'],
            m['num_key_value_heads'], m['head_dim'],
            m['partial_rotary_factor'], m['rope_theta'],
            m['linear_num_key_heads'], m['linear_num_value_heads'],
            m['linear_key_head_dim'], m['linear_value_head_dim'],
            m['linear_conv_kernel_dim'], m['full_attention_interval'],
            m['num_experts_per_tok'], m['moe_intermediate_size'],
            m['shared_expert_intermediate_size'], m['norm_topk_prob'],
            m['rms_norm_eps'], m['tie_word_embeddings'],
            m['decoder_sparse_step'], m['mlp_only_layers']) == \
        (2048, 16, 2, 256, 0.25, 10000000, 16, 32, 128, 128, 4, 4, 10, 512,
         512, True, 1e-6, False, 1, [])
    assert 'EIGHT CHIPS SHARE EACH LAYER' in m['deployment']
    assert set(m['assumed']) == {
        'zero_centred_norms', 'l2norm_eps', 'q_gate_order',
        'A_log_dt_bias_shapes', 'key_head_map', 'rotary', 'router'}
    assert 'float32' in m['changed']['serving_dtype']
    assert "'highest'" in m['changed']['matmul_precision']
    assert m['matmul_precision'] == 'highest'
    assert '9 216' in m['changed']['context']
    assert 'FIELD' in m['changed']['zero_centred_norm']
    assert 'not built' in m['changed']['mtp']


# ---- flops_qwen3next against a count of the parameters ----------------------

def _count(shapes, pick=lambda name: True):
    return sum(int(np.prod(s)) for n, s in shapes.items() if pick(n))


@pytest.mark.parametrize('path', [CONFIG, TOY_CONFIG], ids=[NAME, 'toy'])
def test_flops_qwen3next_counts_what_param_shapes_lists(path):
    m = _json(path)
    f = flops_qwen3next
    shapes = qwen3next.param_shapes(m)
    assert f.param_count(m) == _count(shapes)
    for i in range(m['num_hidden_layers']):
        assert f.layer_param_count(m, i) == _count(
            shapes, lambda k: k.startswith('layer_%d.' % i))
    cfg = qwen3next.lm_config(m, 32, False)
    assert f.kv_bytes_per_token(m) == 2 * cfg.n_attn_layers * cfg.kv_width * 4
    assert (f.n_gdn_layers(m), f.n_full_layers(m)) == (
        cfg.n_gdn_layers, cfg.n_attn_layers)
    from paddle_tpu.models import transformer as T
    pools = T.kv_cache_shapes(cfg, 4, 8, 1)
    # one slot's row of the state pool, and the K - 1 rows that count of
    # the 8 its block of the tail pool holds, are `state_bytes_per_slot`
    assert pools[T.GDN_TAIL][2] == 8
    assert f.state_bytes_per_slot(m) == 4 * (
        int(np.prod(pools[T.GDN_STATE][1:]))
        + int(np.prod(pools[T.GDN_TAIL][1:]))
        * (m['linear_conv_kernel_dim'] - 1) // 8)
    # one row: every weight but the table and the untouched experts, the
    # table's one row, the state
    one = f.decode_bytes_per_step(m, 0, 1)
    e = f.expected_experts_touched(m, 1)
    assert e == pytest.approx(m['num_experts'] * m['num_experts_per_tok']
                              / f.router_width(m))
    n = m['num_hidden_layers']
    assert one == pytest.approx(
        4 * (_count(shapes) - m['vocab_size'] * m['hidden_size']
             + m['hidden_size']
             - n * (m['num_experts'] - e) * f.expert_param_count(m))
        + 2 * f.state_bytes_per_slot(m))
    assert f.decode_bytes_per_step(m, 100, 1) - one == pytest.approx(
        100 * f.kv_bytes_per_token(m))
    # three matrices an expert, the gathered row, gate, up, their product,
    # the result
    d, w = m['hidden_size'], m['moe_intermediate_size']
    assert f.grouped_matmul_bytes(m, 3, 10) == 4 * (3 * 3 * d * w
                                                    + 10 * (2 * d + 3 * w))


def test_the_published_configuration_is_what_the_issue_sized():
    m = _json(CONFIG)
    f = flops_qwen3next
    # a layer's 512 experts: 1 610.6 M, which no chip holds; the 64 held
    # 201.3 M; the mixers 33.7 M and 27.3 M; the router 1.05 M; the shared
    # expert with its gate 3.15 M
    assert 512 * f.expert_param_count(m) == 1610612736
    assert 64 * f.expert_param_count(m) == 201326592
    assert f.mixer_param_count(m, False) == pytest.approx(33.7e6, rel=2e-3)
    assert f.mixer_param_count(m, True) == pytest.approx(27.3e6, rel=2e-3)
    assert f.ffn_param_count(m, 0) == 2048 * 512 + 3 * 2048 * 512 + 2048
    assert (f.n_gdn_layers(m), f.n_full_layers(m)) == (6, 2)
    assert 4 * f.param_count(m) == pytest.approx(7.92e9, rel=2e-3)
    # the whole model, as published: 80 B (with the MTP module it names)
    whole = dict(m, **m['reduced_from'])
    assert f.param_count(whole) == pytest.approx(79.7e9, rel=5e-3)
    # the state: 13.17 MB a slot whatever the context
    assert f.state_row_bytes(m) == ROW == 2195456
    assert f.state_bytes_per_slot(m) == 6 * ROW == 13172736
    assert f.kv_bytes_per_token(m) == 8192
    assert 32 * f.kv_bytes_per_token(m) == 262144
    # the step the issue reckoned: ~46 of 64 held experts touched a layer,
    # ~1.7 GB of state read and written, ~1.6 GB of K/V at ~3 k live tokens
    # a slot
    assert f.expected_experts_touched(m, 64) == pytest.approx(45.9, abs=0.2)
    step = f.decode_bytes_per_step(m, 64 * 3000, 64)
    assert step == pytest.approx(9.2e9, rel=0.03)
    assert 2 * 64 * f.state_bytes_per_slot(m) == pytest.approx(1.69e9,
                                                               rel=5e-3)
    assert 64 * 3000 * 8192 == pytest.approx(1.57e9, rel=5e-3)
    cfg = qwen3next.lm_config(m, 9216, False)
    from paddle_tpu.models import transformer as T
    assert T.kv_cache_shapes(cfg, 18432, 32, 64) == {
        'gen_kv_k': (18432, 2, 32, 512), 'gen_kv_v': (18432, 2, 32, 512),
        'gen_gdn_state': (65, 6, 128, 4096),
        'gen_gdn_tail': (65, 6, 8, 8192)}
    assert (cfg.n_head, cfg.n_kv_head, cfg.head_dim, cfg.kv_width,
            cfg.attn_width, cfg.rotary_dim) == (16, 2, 256, 512, 4096, 64)
    assert (cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim,
            cfg.gdn_value_dim, cfg.ssm_conv, cfg.gdn_chunk, cfg.gdn_inner,
            cfg.gdn_conv_width) == (16, 32, 128, 128, 4, 64, 4096, 8192)
    assert cfg.layer_types == ('gdn', 'gdn', 'gdn', 'attention') * 2
    assert (cfg.matmul_precision, cfg.position, cfg.ffn, cfg.expert_form,
            cfg.norm, cfg.rms_eps, cfg.bias, cfg.tie_embeddings,
            cfg.qk_norm, cfg.norm_zero_centred, cfg.attention_gate,
            cfg.shared_expert_gate) == \
        ('highest', 'rope', 'moe', 'gated', 'rms_norm', 1e-6, False, False,
         'head', True, True, True)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.experts_held,
            cfg.expert_width, cfg.shared_expert_width, cfg.moe_score,
            cfg.norm_topk_prob, cfg.n_moe_layers) == \
        (512, 10, (0, 64), 512, 512, 'softmax', True, 8)
    assert cfg.rope(3) == {'theta': 1e7, 'rotary_dim': 64}


def test_init_params_is_seeded_and_takes_the_familys_initialisation():
    m = _json(TOY_CONFIG)
    a = qwen3next.init_params(m, 3000000001)
    b = qwen3next.init_params(m, 3000000001)
    c = qwen3next.init_params(m, 5)
    assert sorted(a) == sorted(qwen3next.param_shapes(m))
    for name, shape in qwen3next.param_shapes(m).items():
        assert tuple(a[name].shape) == tuple(shape)
        np.testing.assert_array_equal(np.asarray(a[name]),
                                      np.asarray(b[name]))
    assert np.abs(np.asarray(a['tok_emb.w'])
                  - np.asarray(c['tok_emb.w'])).max() > 0
    # the zero-centred norms lie round 0, the DeltaNet's plain one round 1
    ln = np.asarray(a['layer_0.ln1.w'])
    assert abs(ln.mean()) < 0.05 and 0.05 < ln.std() < 0.2
    assert abs(np.asarray(a['layer_0.gdn.norm.w']).mean() - 1.0) < 0.15
    assert np.asarray(a['layer_3.attn.qkv.w']).std() == pytest.approx(
        0.02, rel=0.2)
    assert 0.2 < np.asarray(a['layer_0.gdn.conv.w']).std() < 0.4
    # the recurrence: A = -(0 .. 16) a value head, the step's bias round 1
    a_log = np.concatenate([np.asarray(a['layer_%d.gdn.A_log' % i], 'f8')
                            for i in range(3)])
    assert 0 < np.exp(a_log).min() and np.exp(a_log).max() <= 16.0
    assert np.exp(a_log).std() > 1
    bias = np.concatenate([np.asarray(a['layer_%d.gdn.dt.b' % i])
                           for i in range(3)])
    assert abs(bias.mean() - 1.0) < 0.5 and bias.std() > 0.1


def test_the_builder_refuses_by_name_what_it_does_not_build():
    m = _json(TOY_CONFIG)
    for key, value in (('hidden_act', 'gelu'), ('decoder_sparse_step', 2),
                       ('mlp_only_layers', [0]), ('use_sliding_window', True),
                       ('rope_scaling', {'type': 'yarn'}),
                       ('tie_word_embeddings', True),
                       ('norm_topk_prob', False)):
        with pytest.raises(ValueError, match='builds %s=' % key):
            qwen3next.lm_config(dict(m, **{key: value}), 32, False)
    with pytest.raises(ValueError, match='whole periods'):
        qwen3next.lm_config(dict(m, num_hidden_layers=6), 32, False)
    with pytest.raises(ValueError, match='served only'):
        qwen3next.lm_config(m, 32, True)
    with pytest.raises(ValueError, match='beyond the published context'):
        qwen3next.lm_config(m, 1024, False)


# ---- the readers ------------------------------------------------------------

def _reader(name):
    return _load(os.path.join(ROOT, 'benchmark', 'layer_metrics',
                              name + '.py'), 'reader_' + name.replace(
                                  '.', '_'))


M = _json(CONFIG)
PEAKS = {'hbm_bytes_per_s': 819e9, 'bf16_flops_per_s': 197e12}
# a window of 200 decode steps of 6 DeltaNet and 8 expert layers at 60
# active rows; 30 admissions that ran as 160 chunks, 80 000 real prompt
# rows; 46 held experts touched a layer a step, all 64 a prefill chunk
COUNTERS = {'gdn_state_rows_updated_total': 200 * 60 * 6,
            'gdn_prefill_rows_total': 80000 * 6,
            'gdn_state_resumes_total': 130,
            'kv_tokens_read_total': 200 * 60 * 3000 * 2,
            'moe_experts_touched_total': (200 * 46 + 160 * 64) * 8,
            'moe_assignments_total': (200 * 60 + 80000) * 8 * 10,
            'moe_held_assignments_total': (200 * 60 + 80000) * 10,
            'moe_max_expert_rows_total': (200 * 4 + 160 * 20) * 8}
HIST = {'prefill_seconds': (30, 4.0), 'decode_step_seconds': (200, 5.0)}
ROOFLINES = {'gdn_decode_state_roofline': 'mosaic:gdn_decode_update',
             'qwen3next_moe_ffn_roofline': 'mosaic:ragged-dot',
             'gdn_prefill_chunk_roofline': 'mosaic:gdn_prefill_chunk'}


def _traced(**ops):
    return {'counters': COUNTERS, 'config': M, 'peaks': PEAKS,
            'histograms': HIST, 'window_s': 50.0, 'decode_steps': 200,
            'decode_bytes_per_step': flops_qwen3next.decode_bytes_per_step(
                M, 180000, 60),
            'trace': {'window_s': 8.0, 'busy_s': 7.9, 'op_seconds': ops}}


@pytest.mark.parametrize('name', NEW)
def test_a_new_reader_reads_nothing_where_there_is_nothing(name):
    """The parent commit's program (no such counter, no such operation),
    another configuration (Nemotron's and Mellum 2's among them), an
    untraced or a CPU run: nothing to read, nothing raised."""
    read = _reader(name).read
    others = [_json(os.path.join(ROOT, 'benchmark', 'configs', n + '.json'))
              for n in ('nemotron-3-nano-30b-a3b-ep8-l20',
                        'mellum2-12b-a2.5b-l4', 'ai21-jamba2-3b')]
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


def test_gdn_state_step_share_on_made_up_facts():
    read = _reader('gdn_state_step_share').read
    need = flops_qwen3next.decode_bytes_per_step(M, 180000, 60)
    assert read(_traced()) == pytest.approx(
        100.0 * 2 * 60 * 13172736 / need)
    # 64 rows and the issue's ~3 k live tokens a slot: ~18 %
    full = dict(_traced(), counters={
        'gdn_state_rows_updated_total': 200 * 64 * 6},
        decode_bytes_per_step=flops_qwen3next.decode_bytes_per_step(
            M, 64 * 3000, 64))
    assert read(full) == pytest.approx(18.3, abs=0.5)


def test_gdn_decode_state_roofline_on_made_up_facts():
    read = _reader('gdn_decode_state_roofline').read
    need = 2 * 200 * 60 * 6 * ROW
    facts = _traced(**{'mosaic:gdn_decode_update': 0.5,
                       'mosaic:ssm_decode_conv': 0.05,
                       'mosaic:gdn_prefill_chunk': 0.2,
                       'mosaic:paged_decode_attention': 0.1, 'fusion': 0.9})
    # the bytes need need / 50 s / peak of every second; the two kernels
    # run in 0.55 of the trace's 7.9 busy seconds
    assert read(facts) == pytest.approx(
        100.0 * (need / 50.0 / 819e9) / (0.55 / 7.9))
    assert 0 < read(facts) < 100.0
    assert flops_qwen3next.gdn_decode_state_bytes(M, 1) == 2 * ROW
    # a stall of the host inside the trace (its window longer, its busy
    # seconds and the kernels' the same) moves nothing
    stalled = dict(facts, trace=dict(facts['trace'], window_s=11.0))
    assert read(stalled) == read(facts)


def test_qwen3next_moe_ffn_roofline_on_made_up_facts():
    read = _reader('qwen3next_moe_ffn_roofline').read
    need = flops_qwen3next.grouped_matmul_bytes(
        M, COUNTERS['moe_experts_touched_total'],
        COUNTERS['moe_held_assignments_total'])
    facts = _traced(**{'mosaic:ragged-dot': 1.0, 'mosaic:ragged-dot.1': 0.6,
                       'fusion': 0.4})
    assert read(facts) == pytest.approx(
        100.0 * (need / 50.0 / 819e9) / (1.6 / 7.9))
    assert 0 < read(facts) < 100.0
    # the other families' readers ask for their own keys and read nothing
    for other in ('kexaone_moe_ffn_roofline', 'nemotron_moe_ffn_roofline'):
        assert _reader(other).read(facts) is None


def test_gdn_prefill_chunk_roofline_on_made_up_facts():
    read = _reader('gdn_prefill_chunk_roofline').read
    f = flops_qwen3next
    # a row a value head: K K^T and Q K^T, the solve as one product, W S, Q
    # S and the state's update, the inner product with V'
    per_row = 2 * (2 * 64 * 128 + 64 * 256 + 3 * 128 * 128 + 64 * 128)
    assert f.gdn_prefill_chunk_flops(M, 1) == 32 * per_row
    need = f.gdn_prefill_chunk_flops(M, COUNTERS['gdn_prefill_rows_total'])
    facts = _traced(**{'mosaic:gdn_prefill_chunk': 0.4, 'fusion': 0.4})
    assert read(facts) == pytest.approx(
        100.0 * (need / 50.0 / 197e12) / (0.4 / 7.9))
    assert 0 < read(facts) < 100.0 / 6      # six passes a product at most


def test_the_accepted_readers_the_cell_lists_read_this_configuration():
    """`decode_step_ms` and the straggler expert read this configuration's
    facts; the other state families' readers, keyed to their own keys,
    read nothing here."""
    facts = _traced()
    assert _reader('decode_step_ms').read(facts) == pytest.approx(25.0)
    # the reader divides by the experts HELD (the key `num_experts`), the
    # router chooses among 512: the reading is an eighth of the busiest
    # held expert's rows over the mean's (PERF.md section 3)
    got = _reader('moe_load_max_over_mean').read(facts)
    assert got == pytest.approx(
        COUNTERS['moe_max_expert_rows_total']
        / (COUNTERS['moe_assignments_total'] / 64.0))
    every = _traced(**{'mosaic:ssm_decode_update': 0.2,
                       'mosaic:ssd_decode_update': 0.2,
                       'mosaic:ssm_decode_conv': 0.2,
                       'mosaic:ssm_prefill_scan': 0.2})
    for name in ('ssm_decode_state_roofline', 'ssm_prefill_scan_roofline',
                 'ssm_state_step_share', 'ssd_decode_state_roofline',
                 'ssd_state_step_share', 'kv_window_read_share'):
        assert _reader(name).read(every) is None


@pytest.mark.parametrize('name,counters', [
    ('gdn_decode_state_roofline',
     lambda s: {'gdn_state_rows_updated_total': int(819e9 * s / (2 * ROW))}),
    ('qwen3next_moe_ffn_roofline',
     lambda s: {'moe_experts_touched_total': int(
         819e9 * s / (4 * 3 * 2048 * 512))}),
    ('gdn_prefill_chunk_roofline',
     lambda s: {'gdn_prefill_rows_total': int(
         197e12 * s / flops_qwen3next.gdn_prefill_chunk_flops(M, 1))})])
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

def test_qwen3next_control_main_at_toy_width(capsys):
    from benchmark.reference import qwen3next_control
    rc = qwen3next_control.main([
        TOY_CONFIG, os.path.join(HERE, 'traffic', 'toy-serve-qwen3next.json'),
        '5', '3000000009'])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith('{')]
    assert rc == 0 and len(lines) == 4
    always = {'bfloat16', 'no-decay', 'beta-1', 'no-l2norm',
              'tiled-key-heads', 'rotate-all', 'no-attention-gate',
              'ungated-shared-expert', '9-experts', 'plain-norm',
              'stale-state'}
    for out in lines:
        n = out['prompt_len']
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
        assert set(out['controls']) == always | (
            {'chunk-edge'} if n > 16 else set())
        for name, reading in out['controls'].items():
            assert reading['logits_vs_ref'][1] > 2e-5, name
            assert set(reading['refused_by']) <= {'logits', 'tokens'}
            assert reading['greedy_margin_check_rows'] <= \
                reading['greedy_margin_worst']
