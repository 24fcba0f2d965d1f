"""The K-EXAONE-236B-A23B configuration's benchmark files (ISSUE 41): a toy
cell with the new builder through run.py end to end on the CPU (its own
toy manifest; prompts chunked over the widest bucket wrap the window
layers' rings), the manifest's entries and the published file against the
catalog's row, the traffic letter for letter, flops_kexaone's formulae
against a count of param_shapes and against the issue's table, the three
new readers and the accepted readers the cell is listed under on made-up
facts of this configuration (no roofline over 100 on a trace that spends
its whole window in the kernel at peak), and the comparison script's
main() at toy width."""
import json
import os

import numpy as np
import pytest

from benchmark import flops_kexaone, traffic_gen
from benchmark.models import kexaone

from test_bench_olmoe import _last_json, _load, run_on_cpu   # noqa: F401
from test_bench_run import MANIFEST, by_name, check_config_entry

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TOY_MANIFEST = os.path.join(HERE, 'fixtures', 'BENCHMARK.toy.kexaone.json')
TOY_CONFIG = os.path.join(HERE, 'configs', 'toy-kexaone.json')
CONFIG = os.path.join(ROOT, 'benchmark', 'configs',
                      'k-exaone-236b-a23b-ep16-l5.json')
TRAFFIC = os.path.join(ROOT, 'benchmark', 'traffic', 'mixed64-closed.json')
CELL = 'kexaone-serve-mixed64'
CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'
NEW = ('window_decode_attention_roofline', 'kv_window_read_share',
       'kexaone_moe_ffn_roofline')


def _json(path):
    with open(path) as f:
        return json.load(f)


# ---- the toy cell through run.py --------------------------------------------

def test_end_to_end_line(run_on_cpu, capsys):              # noqa: F811
    rc = run_on_cpu.main(['--workload', 'toy-serve-kexaone', '--seed',
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
    rc = run_on_cpu.main(['--workload', 'toy-serve-kexaone', '--seed', '7',
                          '--seconds', '0.7', '--trace', '1'],
                         manifest_path=TOY_MANIFEST)
    out, lines = _last_json(capsys)
    assert rc == 0 and out['correct'] is True
    # on the CPU no operation of the trace is a Mosaic kernel and
    # peak_hbm_gb.serve has nothing to read: the three rooflines and the
    # peak return nothing
    assert set(out['metrics']) == {
        'decode_step_ms', 'decode_hbm_share', 'device_idle_share.serve',
        'moe_experts_touched_share', 'moe_held_assignment_share',
        'kv_window_read_share'}
    # one global layer in five reads every live key (10 to 64), four read
    # 12 at most: between a fifth and all
    assert 20.0 < out['metrics']['kv_window_read_share']['value'] < 100.0
    # 4 of 16 experts held: a quarter of the assignments under even routing
    assert 5.0 < out['metrics']['moe_held_assignment_share']['value'] < 60.0
    assert 0 < out['metrics']['decode_hbm_share']['value'] < 100
    facts = json.loads([ln for ln in lines
                        if ln.startswith('facts: ')][-1][len('facts: '):])
    assert facts['decode_bytes_per_step'] > 0


# ---- the manifest and the published file ------------------------------------

def check_kexaone_entry(manifest):
    """The configuration, its cell and the metrics that list the cell,
    each found by name: where they stand in their lists is
    test_bench_manifest.py's to hold (appended, never put in)."""
    conf = by_name(manifest['configs'], 'k-exaone-236b-a23b-ep16-l5')
    check_config_entry(conf, manifest)
    assert conf['reduced'] == ['num_hidden_layers', 'num_experts',
                               'vocab_size', 'layer_types',
                               'mlp_layer_types', 'sliding_windows']
    cell = by_name(manifest['workloads'], CELL)
    assert cell == dict(cell, name=CELL, config=conf['name'],
                        traffic='mixed64-closed', chips=1)
    listed = {x['name'] for x in manifest['end_to_end']
              + manifest['per_layer'] if CELL in x.get('workloads', ())}
    # `itl_p95_ms` and every per-layer metric that moves it and whose
    # reader asks nothing of the configuration, and its own three. At
    # least: a later PR may append
    assert listed >= {
        'itl_p95_ms', 'decode_step_ms', 'decode_host_gap_ms',
        'decode_host_gap_ms.admit', 'decode_host_gap_ms.feed',
        'decode_host_gap_ms.dispatch', 'decode_host_gap_ms.deliver',
        'server_loop_unaccounted_share', 'decode_sampled_step_share',
        'admission_ms', 'admission_ms.dispatch', 'admission_ms.drain',
        'admission_ms.fetch', 'token_gap_ms.admission', 'token_gap_ms.plain',
        'token_gap_admission_share'} | set(NEW)
    # NOT under `serve_tokens_per_s`: a 50 s window holds 2.3 of the 8
    # requests of a client's cycle, which ones is the seed's, and six
    # seeds spread 4.4 % where 2.5 admits a cell (PERF.md 6, the driver's
    # verdict on PR 41) — so under no per-layer metric that moves it
    # either: a metric lists the cells that report what it moves
    by = {x['name']: x for x in manifest['per_layer']}
    assert 'serve_tokens_per_s' not in listed
    assert {by[n]['moves'] for n in listed if n in by} == {'itl_p95_ms'}
    # NOT under a reader that would misread this configuration's keys
    assert not listed & {'moe_ffn_hbm_share', 'moe_held_ffn_hbm_share',
                         'moe_load_max_over_mean', 'lfm2_moe_ffn_roofline',
                         'mla_decode_attention_hbm_share'}
    for name in NEW:
        assert CELL in by_name(manifest['per_layer'], name)['workloads']


def test_config_entry_admits_the_new_entry():
    check_kexaone_entry(MANIFEST)


def test_the_published_file_keeps_every_number_of_the_catalogs_row():
    if not os.path.isfile(CATALOG):
        pytest.skip('no catalog on this machine')
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f)
                if r['name'] == 'K-EXAONE-236B-A23B']
    m = _json(CONFIG)
    differs = {k for k, v in row['config'].items() if m.get(k, '?') != v}
    assert differs == set(m['reduced']) == set(m['reduced_from'])
    assert all(m['reduced_from'][k] == row['config'][k] for k in differs)
    assert m['source'].startswith(row['source_url'])
    # the first period and the layer behind it: window window window
    # global window, the dense layer and four expert layers
    for key in ('layer_types', 'mlp_layer_types', 'sliding_windows'):
        assert m[key] == row['config'][key][:5]
    assert m['layer_types'] == ['sliding_attention'] * 3 + [
        'full_attention', 'sliding_attention']
    assert (m['num_hidden_layers'], m['first_k_dense_replace'],
            m['num_experts'], m['first_expert_held'], m['vocab_size']) == \
        (5, 1, 8, 0, 19200)
    # every width of the row
    assert (m['hidden_size'], m['num_attention_heads'],
            m['num_key_value_heads'], m['head_dim'], m['intermediate_size'],
            m['moe_intermediate_size'], m['num_experts_per_tok'],
            m['routed_scaling_factor'], m['sliding_window'],
            m['rope_parameters']['rope_theta'], m['rms_norm_eps']) == \
        (6144, 64, 8, 128, 18432, 2048, 8, 2.5, 128, 1000000, 1e-5)
    assert m['reduced_from']['num_experts'] == 128
    assert m['deployment'].strip() and m['changed']
    assert set(m['assumed']) == {'qk_norm', 'norm_placement',
                                 'e_score_correction_bias'}


def test_the_traffic_is_the_issues_letter_for_letter():
    tr = _json(TRAFFIC)
    assert tr['kind'] == 'serve'
    assert tr['arrival'] == {'kind': 'closed', 'clients': 64,
                             'stagger_s': 0.1}
    assert tr['prompt_len'] == {'dist': 'lognormal', 'median': 1024,
                                'sigma': 0.9, 'min': 128, 'max': 4096}
    assert tr['output_len'] == {'dist': 'lognormal', 'median': 512,
                                'sigma': 0.5, 'min': 128, 'max': 1024}
    assert tr['engine'] == {'paged': True, 'slots': 64, 'block_size': 32,
                            'max_len': 5120,
                            'prompt_buckets': [128, 256, 512],
                            'num_blocks': 10240}
    assert (tr['pool_size'], tr['sampling'], tr['shared_prefix_len'],
            tr['group_size'], tr['check_new_tokens'], tr['trace_seconds']) \
        == (512, 'greedy', 0, 0, 8, 3.0)
    # eight requests a client; the check's two prompts are the pool's
    # shortest and longest
    plen = traffic_gen.length_pool(tr['prompt_len'], tr['pool_size'])
    assert (plen.min(), plen.max()) == (128, 4096)
    assert 1350 < plen.mean() < 1450
    # every slot's worst case fits the global layer's pool, and the longest
    # request the table
    assert tr['engine']['num_blocks'] * 32 == 64 * 5120
    assert tr['prompt_len']['max'] + tr['output_len']['max'] \
        <= tr['engine']['max_len']


# ---- flops_kexaone against a count of the parameters ------------------------

def _count(shapes, pick=lambda name: True):
    return sum(int(np.prod(s)) for n, s in shapes.items() if pick(n))


@pytest.mark.parametrize('path', [CONFIG, TOY_CONFIG],
                         ids=['k-exaone-236b-a23b-ep16-l5', 'toy-kexaone'])
def test_flops_kexaone_counts_what_param_shapes_lists(path):
    m = _json(path)
    shapes = kexaone.param_shapes(m)
    n, dense = m['num_hidden_layers'], m['first_k_dense_replace']
    assert flops_kexaone.param_count(m) == _count(shapes)
    for i in range(n):
        assert flops_kexaone.layer_param_count(m, i) == _count(
            shapes, lambda k: k.startswith('layer_%d.' % i))
    routed = lambda k: '.moe.' in k and 'router' not in k \
        and 'shared' not in k                                 # noqa: E731
    assert flops_kexaone.expert_param_count(m) * m['num_experts'] \
        * (n - dense) == _count(shapes, routed)
    cfg = kexaone.lm_config(m, 32, False)
    assert flops_kexaone.kv_bytes_per_token(m) == \
        2 * cfg.n_attn_layers * cfg.kv_width * 4
    assert flops_kexaone.window_bytes_per_slot(m) == \
        2 * cfg.n_window_layers * cfg.sliding_window * cfg.kv_width * 4
    # one live row reads what it touches of the held experts; very many
    # rows every weight but the table, of which a row a slot
    one = flops_kexaone.decode_bytes_per_step(m, 0, 1)
    rest = _count(shapes, lambda k: not routed(k) and k != 'tok_emb.w')
    k_of_e = m['num_experts_per_tok'] / flops_kexaone.router_width(m)
    assert one == pytest.approx(4 * (
        rest + m['hidden_size'] + (n - dense) * m['num_experts'] * k_of_e
        * flops_kexaone.expert_param_count(m))
        + flops_kexaone.window_bytes_per_slot(m))
    assert flops_kexaone.decode_bytes_per_step(m, 100, 1) - one == \
        100 * flops_kexaone.kv_bytes_per_token(m)


def test_the_published_configuration_is_what_the_issue_sized():
    m = _json(CONFIG)
    f = flops_kexaone
    assert f.attention_param_count(m) == pytest.approx(113.26e6, rel=1e-3)
    assert 3 * 6144 * 18432 == pytest.approx(339.74e6, rel=1e-3)
    assert f.expert_param_count(m) == pytest.approx(37.75e6, rel=1e-3)
    assert 4 * f.layer_param_count(m, 0) == pytest.approx(1.812e9, rel=1e-3)
    assert 4 * f.layer_param_count(m, 1) == pytest.approx(1.815e9, rel=1e-3)
    assert 4 * f.param_count(m) == pytest.approx(10.02e9, rel=1e-3)
    # a whole expert layer, every expert of 128: no one-chip cut holds it
    whole = dict(m, num_experts=128)
    assert 4 * f.layer_param_count(whole, 1) == pytest.approx(19.93e9,
                                                              rel=1e-3)
    # the cache: 8192 B a token a layer; the global layer's pool every
    # slot's worst case, the four window layers' 64 rings of 6 blocks
    assert f.kv_row_bytes(m) == f.kv_bytes_per_token(m) == 8192
    assert 10240 * 32 * f.kv_bytes_per_token(m) == pytest.approx(2.68e9,
                                                                 rel=2e-3)
    cfg = kexaone.lm_config(m, 5120, False)
    from paddle_tpu.models import transformer as T
    assert T.window_ring(cfg, 32) == 6
    assert T.kv_cache_shapes(cfg, 10240, 32, 64) == {
        'gen_kv_k': (10240, 1, 32, 1024), 'gen_kv_v': (10240, 1, 32, 1024),
        'gen_kv_window_k': (385, 4, 32, 1024),
        'gen_kv_window_v': (385, 4, 32, 1024)}
    assert 2 * 385 * 4 * 32 * 1024 * 4 == pytest.approx(0.40e9, rel=1e-2)
    # 64 rows x 8 of 128: nearly every held expert touched a layer-step
    assert f.expected_experts_touched(m, 64) == pytest.approx(7.87, abs=0.01)
    assert f.decode_bytes_per_step(m, 135000, 64) == pytest.approx(
        10.84e9, rel=5e-3)
    assert f.window_decode_attention_bytes(m, 1) == 8192
    assert f.window_decode_attention_flops(m, 1) == 4 * 64 * 128
    assert (cfg.n_head, cfg.n_kv_head, cfg.head_dim, cfg.kv_width,
            cfg.attn_width) == (64, 8, 128, 1024, 8192)
    assert cfg.layer_types == ('window',) * 3 + ('attention', 'window')
    assert (cfg.n_attn_layers, cfg.n_window_layers, cfg.sliding_window,
            cfg.global_rope) == (1, 4, 128, False)
    assert [cfg.rotates(i) for i in range(5)] == [True] * 3 + [False, True]
    assert (cfg.n_experts, cfg.experts_held, cfg.experts_per_token,
            cfg.expert_width, cfg.n_shared_experts, cfg.n_dense_layers,
            cfg.d_ff) == (128, (0, 8), 8, 2048, 1, 1, 18432)
    assert (cfg.moe_score, cfg.routed_scale, cfg.norm_topk_prob,
            cfg.qk_norm, cfg.tie_embeddings) == \
        ('sigmoid', 2.5, True, 'head', False)
    assert not cfg.bias and cfg.rope_theta == 1e6 and cfg.rms_eps == 1e-5
    with pytest.raises(ValueError):
        kexaone.lm_config(m, 5120, True)                # served only
    for key, other in (('hidden_act', 'gelu'), ('scoring_func', 'softmax'),
                       ('n_group', 2), ('tie_word_embeddings', True),
                       ('sliding_windows', [128] * 5),
                       ('mlp_layer_types', ['sparse'] * 5),
                       ('rope_parameters', {'rope_theta': 1e6,
                                            'rope_type': 'yarn'})):
        with pytest.raises(ValueError, match=key):
            kexaone.lm_config(dict(m, **{key: other}), 5120, False)


def test_init_params_is_seeded_and_gives_norms_and_bias_a_spread():
    m = _json(TOY_CONFIG)
    a = kexaone.init_params(m, 3000000001)
    b = kexaone.init_params(m, 3000000001)
    c = kexaone.init_params(m, 5)
    assert sorted(a) == sorted(kexaone.param_shapes(m))
    for name, shape in kexaone.param_shapes(m).items():
        assert tuple(a[name].shape) == tuple(shape)
        np.testing.assert_array_equal(np.asarray(a[name]),
                                      np.asarray(b[name]))
    assert np.abs(np.asarray(a['tok_emb.w'])
                  - np.asarray(c['tok_emb.w'])).max() > 0
    ln = np.asarray(a['layer_2.ln1.w'])
    assert abs(ln.mean() - 1.0) < 0.05 and 0.05 < ln.std() < 0.2
    assert 0.002 < np.asarray(a['layer_2.moe.router.bias']).std() < 0.03
    assert np.asarray(a['layer_0.attn.qkv.w']).std() == pytest.approx(
        0.02, rel=0.2)
    assert a['layer_1.moe.gate.w'].shape[0] == m['num_experts'] == 4
    assert a['layer_1.moe.router.w'].shape[1] == 16


# ---- the readers ------------------------------------------------------------

def _reader(name):
    return _load(os.path.join(ROOT, 'benchmark', 'layer_metrics',
                              name + '.py'), 'reader_' + name.replace(
                                  '.', '_'))


M = _json(CONFIG)
PEAKS = {'hbm_bytes_per_s': 819e9, 'bf16_flops_per_s': 197e12}
# a window of 100 decode steps of 4 expert layers, 64 rows x 8 of 128 a
# layer-step of which 8 of 128 held: 32 computed assignments, every held
# expert touched; 64 x 2100 live positions x 1 global layer and 64 x 128 x
# 4 window layers a step
COUNTERS = {'moe_layer_steps_total': 400, 'moe_assignments_total': 204800,
            'moe_held_assignments_total': 12800,
            'moe_experts_touched_total': 3200,
            'moe_max_expert_rows_total': 2400,
            'kv_tokens_read_total': 100 * 64 * 2100,
            'kv_window_tokens_read_total': 100 * 64 * 128 * 4}
ROOFLINES = ('window_decode_attention_roofline', 'kexaone_moe_ffn_roofline')


def _traced(**ops):
    return {'counters': COUNTERS, 'config': M, 'peaks': PEAKS,
            'window_s': 4.0, 'trace': {'window_s': 2.0, 'busy_s': 1.8,
                                       'op_seconds': ops}}


@pytest.mark.parametrize('name', NEW)
def test_a_new_reader_reads_nothing_where_there_is_nothing(name):
    """The parent commit's program (no such counter), another
    configuration, an untraced or a CPU run: nothing to read, nothing
    raised."""
    read = _reader(name).read
    lfm2 = _json(os.path.join(ROOT, 'benchmark', 'configs',
                              'lfm2-8b-a1b-l8.json'))
    old = {k: v for k, v in COUNTERS.items()
           if k != 'kv_window_tokens_read_total'}
    both = {'mosaic:ragged-dot-none': 1.0,
            'mosaic:paged_window_decode_attention': 0.2,
            'mosaic:paged_decode_attention': 0.2}
    for facts in ({}, {'counters': {}, 'config': M},
                  {'counters': {}, 'config': {'d_model': 8}, 'trace': None},
                  dict(_traced(**both), config=lfm2),
                  dict(_traced(**both), config={'hidden_size': 8}),
                  dict(_traced(**both), counters={})):
        assert read(facts) is None
    if name != 'kexaone_moe_ffn_roofline':
        # the parent's program on this configuration: no window counter
        assert read(dict(_traced(**both), counters=old)) is None
    if name in ROOFLINES:
        assert read(_traced(fusion=0.5)) is None    # no such operation
        assert read(dict(_traced(), trace=None)) is None
        # the global layers' kernel is not the window layers'
        assert _reader('window_decode_attention_roofline').read(_traced(
            **{'mosaic:paged_decode_attention': 0.2})) is None


def test_kv_window_read_share_on_made_up_facts():
    read = _reader('kv_window_read_share').read
    # one layer reads 2100 keys a slot, four read 128: of 5 x 2100
    assert read({'counters': COUNTERS, 'config': M}) == pytest.approx(
        100.0 * (2100 + 4 * 128) / (5 * 2100))
    assert 24.0 < read({'counters': COUNTERS, 'config': M}) < 25.0
    # contexts inside the window: every layer reads every key
    short = dict(COUNTERS, kv_tokens_read_total=6400 * 100,
                 kv_window_tokens_read_total=6400 * 100 * 4)
    assert read({'counters': short, 'config': M}) == pytest.approx(100.0)


def test_window_decode_attention_roofline_on_made_up_facts():
    read = _reader('window_decode_attention_roofline').read
    need = 100 * 64 * 128 * 4 * 8192
    facts = _traced(**{'mosaic:paged_window_decode_attention': 0.1,
                       'mosaic:paged_decode_attention': 0.5,
                       'mosaic:ragged-dot-none': 1.0, 'fusion': 0.4})
    # the bytes need need / 4 s / peak of every second; the kernel runs in
    # 0.1 / 2 of every second
    assert read(facts) == pytest.approx(
        100.0 * (need / 4.0 / 819e9) / (0.1 / 2.0))
    assert 0 < read(facts) < 100.0
    # the FLOP share beside it stays far under 100: 4 FLOP a byte
    flops = flops_kexaone.window_decode_attention_flops(
        M, 100 * 64 * 128 * 4)
    assert 100.0 * (flops / 4.0 / 197e12) / (0.1 / 2.0) < 5.0


def test_kexaone_moe_ffn_roofline_on_made_up_facts():
    read = _reader('kexaone_moe_ffn_roofline').read
    need = flops_kexaone.grouped_matmul_bytes(M, 3200, 12800)
    # 3 200 touched experts x 151 MB is what counts; activations ~0.1 %
    assert need == pytest.approx(3200 * 3 * 6144 * 2048 * 4, rel=0.01)
    facts = _traced(**{'mosaic:ragged-dot-none': 1.2,
                       'mosaic:ragged-dot-metadata': 0.1, 'fusion': 0.5})
    assert read(facts) == pytest.approx(
        100.0 * (need / 4.0 / 819e9) / (1.3 / 2.0))
    assert 0 < read(facts) < 100.0
    # the readers the cell is NOT listed under, on this configuration's
    # keys: OLMoE's counts an expert at the dense layer's width, nine times
    # the bytes; LFM2's counts the assignments computed elsewhere too
    from benchmark import flops_lfm2, flops_moe
    assert flops_moe.grouped_matmul_bytes(M, 3200, 0) == pytest.approx(
        9 * flops_kexaone.grouped_matmul_bytes(M, 3200, 0))
    assert flops_lfm2.grouped_matmul_bytes(M, 0, 204800) == pytest.approx(
        16 * flops_kexaone.grouped_matmul_bytes(M, 0, 12800))


def test_the_accepted_readers_the_cell_lists_read_this_configuration():
    """`moe_held_assignment_share`: 8 of 128 experts held, 6.25 % under
    even routing; `moe_experts_touched_share` divides by the file's
    `num_experts`, the 8 held: 100 when every held expert is touched;
    `moe_load_max_over_mean`, which the cell is NOT listed under, divides
    ALL the router's assignments by it and reads a sixteenth of the
    truth."""
    facts = {'counters': COUNTERS, 'config': M}
    assert _reader('moe_held_assignment_share').read(facts) == \
        pytest.approx(6.25)
    assert _reader('moe_experts_touched_share').read(facts) == \
        pytest.approx(100.0)
    skew = _reader('moe_load_max_over_mean').read(facts)
    # the busiest held expert had 6 rows a layer-step of a mean of 4
    assert skew == pytest.approx(1.5 / 16)
    # paged_decode_attention_roofline (the global layer's kernel; the cell
    # waits for a benchmark PR to be listed: PERF.md section 7) reads the
    # global layer's rows alone at this configuration's row width
    read = _reader('paged_decode_attention_roofline').read
    traced = _traced(**{'mosaic:paged_decode_attention': 0.5,
                        'mosaic:paged_window_decode_attention': 0.1})
    assert read(traced) == pytest.approx(
        100.0 * (100 * 64 * 2100 * 8192 / 4.0 / 819e9) / (0.5 / 2.0))
    assert 0 < read(traced) < 100.0


@pytest.mark.parametrize('name,op,counters', [
    ('window_decode_attention_roofline',
     'mosaic:paged_window_decode_attention',
     lambda s: {'kv_window_tokens_read_total': int(819e9 * s / 8192)}),
    ('kexaone_moe_ffn_roofline', 'mosaic:ragged-dot-none',
     lambda s: {'moe_experts_touched_total':
                int(819e9 * s / (3 * 6144 * 2048 * 4)),
                'moe_held_assignments_total': 0})])
def test_a_roofline_never_passes_100_at_peak(name, op, counters):
    """A made-up trace that spends its WHOLE window in the kernel, moving
    bytes at the chip's peak all the while: exactly the roofline, and
    less for any time beside it."""
    read = _reader(name).read
    facts = {'counters': counters(4.0), 'config': M, 'peaks': PEAKS,
             'window_s': 4.0, 'trace': {'window_s': 2.0, 'busy_s': 2.0,
                                        'op_seconds': {op: 2.0}}}
    assert 99.9 < read(facts) <= 100.0
    facts['trace']['op_seconds'] = {op: 1.0}       # twice peak: impossible
    assert read(facts) > 105.0                     # and it shows, unclipped


# ---- the comparison script, as the chip runs it -----------------------------

def test_kexaone_control_main_at_toy_width(capsys):
    from benchmark.reference import kexaone_control
    rc = kexaone_control.main([
        TOY_CONFIG, os.path.join(HERE, 'traffic', 'toy-serve-kexaone.json'),
        '5', '3000000009'])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith('{')]
    assert rc == 0 and len(lines) == 4
    for out in lines:
        assert out['rows'] == min(25, 72 - out['prompt_len'] + 1)
        assert out['logits_vs_ref'][1] < 1e-4
        assert out['logits_vs_ref_given_routing'][1] < 1e-4
        assert out['refused_by_logits_rms'] is False
        assert out['greedy_margin_worst'] == 0.0
        assert set(out['controls']) == {
            'no-window', 'window-11', 'window-13', 'rope-on-global',
            'bfloat16', 'held-3', 'no-norm-weights'}
        for name, reading in out['controls'].items():
            if out['prompt_len'] + out['rows'] > 14 or 'window' not in name:
                assert reading['logits_vs_ref'][1] > 5e-4, name
