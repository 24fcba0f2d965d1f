"""The LFM2-8B-A1B configuration's benchmark files (ISSUE 35): a toy cell
with the new builder through run.py end to end on the CPU, prefix sharing
ON (its own toy manifest), the manifest's entries and the published file
against the catalog's row, flops_lfm2's formulae against a count of
param_shapes and against the issue's table, the three new readers on
made-up facts (the two rooflines never over 100 on a trace that spends
its whole window in the kernel at peak), and the comparison script's
main() at toy width."""
import json
import os

import numpy as np
import pytest

from benchmark import flops_lfm2
from benchmark.models import lfm2

from test_bench_olmoe import _last_json, _load, run_on_cpu   # noqa: F401
from test_bench_run import MANIFEST, by_name, check_config_entry

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TOY_MANIFEST = os.path.join(HERE, 'fixtures', 'BENCHMARK.toy.lfm2.json')
TOY_CONFIG = os.path.join(HERE, 'configs', 'toy-lfm2.json')
CONFIG = os.path.join(ROOT, 'benchmark', 'configs', 'lfm2-8b-a1b-l8.json')
TRAFFIC = os.path.join(ROOT, 'benchmark', 'traffic', 'agent64-closed.json')
CELL = 'lfm2-serve-agent64'
CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'


def _json(path):
    with open(path) as f:
        return json.load(f)


# ---- the toy cell through run.py 

def test_end_to_end_line(run_on_cpu, capsys):              # noqa: F811
    rc = run_on_cpu.main(['--workload', 'toy-serve-lfm2', '--seed',
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
    rc = run_on_cpu.main(['--workload', 'toy-serve-lfm2', '--seed', '7',
                          '--seconds', '0.7', '--trace', '1'],
                         manifest_path=TOY_MANIFEST)
    out, lines = _last_json(capsys)
    assert rc == 0 and out['correct'] is True
    # on the CPU no operation of the trace is a Mosaic kernel and
    # peak_hbm_gb.serve has nothing to read: those readers return nothing
    # (the two rooflines, and OLMoE's moe_ffn_hbm_share beside them)
    assert set(out['metrics']) == {
        'decode_step_ms', 'decode_hbm_share', 'device_idle_share.serve',
        'moe_experts_touched_share', 'moe_load_max_over_mean',
        'prefix_hit_token_share'}
    # every request shares its first 16 tokens (two blocks of 8) of 18-30
    assert 50.0 < out['metrics']['prefix_hit_token_share']['value'] < 90.0
    assert 0 < out['metrics']['decode_hbm_share']['value'] < 100
    facts = json.loads([ln for ln in lines
                        if ln.startswith('facts: ')][-1][len('facts: '):])
    assert facts['decode_bytes_per_step'] > 0


# ---- the manifest and the published file 

def check_lfm2_entry(manifest):
    """The configuration, its cell and the metrics that list the cell,
    each found by name: where they stand in their lists is
    test_bench_manifest.py's to hold (appended, never put in)."""
    conf = by_name(manifest['configs'], 'lfm2-8b-a1b-l8')
    check_config_entry(conf, manifest)
    assert conf['reduced'] == ['num_hidden_layers', 'layer_types']
    cell = by_name(manifest['workloads'], CELL)
    assert cell == dict(cell, name=CELL, config=conf['name'],
                        traffic='agent64-closed', chips=1)
    # under every serve metric whose reader asks nothing of the
    # configuration, the two expert readers that read `num_experts`, and
    # its own three — NOT under moe_ffn_hbm_share, whose byte count reads
    # `intermediate_size` as an expert's width (here the dense layers')
    listed = {x['name'] for x in manifest['end_to_end']
              + manifest['per_layer'] if CELL in x.get('workloads', ())}
    assert listed == {
        'serve_tokens_per_s', 'itl_p95_ms', 'decode_step_ms',
        'decode_hbm_share', 'decode_host_gap_ms', 'decode_host_gap_ms.admit',
        'decode_host_gap_ms.feed', 'decode_host_gap_ms.dispatch',
        'decode_host_gap_ms.deliver', 'server_loop_unaccounted_share',
        'device_idle_share.serve', 'peak_hbm_gb.serve',
        'ttft_p95_unbounded_ms', 'ttft_mean_unbounded_ms',
        'decode_sampled_step_share', 'decode_overlapped_step_share',
        'moe_experts_touched_share', 'moe_load_max_over_mean',
        'paged_decode_attention_roofline', 'lfm2_moe_ffn_roofline',
        'prefix_hit_token_share'}
    for name in ('paged_decode_attention_roofline', 'lfm2_moe_ffn_roofline',
                 'prefix_hit_token_share'):
        assert by_name(manifest['per_layer'], name)['workloads'] == [CELL]


def test_config_entry_admits_the_new_entry():
    check_lfm2_entry(MANIFEST)


def test_the_published_file_keeps_every_number_of_the_catalogs_row():
    if not os.path.isfile(CATALOG):
        pytest.skip('no catalog on this machine')
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f) if r['name'] == 'LFM2-8B-A1B']
    m = _json(CONFIG)
    differs = {k for k, v in row['config'].items() if m.get(k, '?') != v}
    assert differs == set(m['reduced']) == set(m['reduced_from'])
    assert all(m['reduced_from'][k] == row['config'][k] for k in differs)
    assert m['source'].startswith(row['source_url'])
    # the first two periods of the published order, both dense layers and
    # every expert
    assert m['layer_types'] == row['config']['layer_types'][:8] == [
        'conv', 'conv', 'full_attention', 'conv'] * 2
    assert (m['num_hidden_layers'], m['num_dense_layers'],
            m['num_experts']) == (8, 2, 32)
    assert m['deployment'].strip() and m['changed']
    assert set(m['assumed']) == {'tie_word_embeddings', 'head_dim'}


def test_the_traffic_is_the_issues_letter_for_letter():
    tr = _json(TRAFFIC)
    assert tr['arrival'] == {'kind': 'closed', 'clients': 64,
                             'stagger_s': 0.1}
    assert tr['prompt_len'] == {'dist': 'uniform', 'min': 4160, 'max': 4608}
    assert tr['output_len'] == {'dist': 'lognormal', 'median': 256,
                                'sigma': 0.5, 'min': 64, 'max': 512}
    assert tr['engine'] == {'paged': True, 'slots': 64, 'block_size': 32,
                            'max_len': 5120,
                            'prompt_buckets': [128, 256, 512],
                            'num_blocks': 4096}
    assert (tr['sampling'], tr['shared_prefix_len'], tr['group_size'],
            tr['pool_size'], tr['check_new_tokens'],
            tr['trace_seconds']) == ('greedy', 4096, 1024, 1024, 8, 3.0)
    # the prefix is whole blocks, every bucket is whole blocks, and the
    # longest request fits the table
    assert tr['shared_prefix_len'] % 32 == 0
    assert all(b % 32 == 0 for b in tr['engine']['prompt_buckets'])
    assert tr['prompt_len']['max'] + tr['output_len']['max'] \
        <= tr['engine']['max_len']


# ---- flops_lfm2 against a count of the parameters 

def _count(shapes, pick=lambda name: True):
    return sum(int(np.prod(s)) for n, s in shapes.items() if pick(n))


@pytest.mark.parametrize('path', [CONFIG, TOY_CONFIG],
                         ids=['lfm2-8b-a1b-l8', 'toy-lfm2'])
def test_flops_lfm2_counts_what_param_shapes_lists(path):
    m = _json(path)
    shapes = lfm2.param_shapes(m)
    n, dense = m['num_hidden_layers'], m['num_dense_layers']
    assert flops_lfm2.param_count(m) == _count(shapes)
    for i in range(n):
        assert flops_lfm2.layer_param_count(m, i) == _count(
            shapes, lambda k: k.startswith('layer_%d.' % i))
    routed = lambda k: '.moe.' in k and 'router' not in k   # noqa: E731
    assert flops_lfm2.expert_param_count(m) * m['num_experts'] \
        * (n - dense) == _count(shapes, routed)
    cfg = lfm2.lm_config(m, 32, False)
    assert flops_lfm2.kv_bytes_per_token(m) == \
        2 * cfg.n_attn_layers * cfg.kv_width * 4
    assert flops_lfm2.tail_bytes_per_block(m) == \
        cfg.n_conv_layers * (cfg.conv_kernel - 1) * cfg.d_model * 4
    # one live row reads k experts a layer; very many rows every weight
    one = flops_lfm2.decode_bytes_per_step(m, 0, 1)
    rest = _count(shapes, lambda k: not routed(k))
    assert one == pytest.approx(4 * (
        rest + (n - dense) * m['num_experts_per_tok']
        * flops_lfm2.expert_param_count(m))
        + 2 * flops_lfm2.tail_bytes_per_block(m))
    assert flops_lfm2.decode_bytes_per_step(m, 100, 1) - one == \
        100 * flops_lfm2.kv_bytes_per_token(m)


def test_the_published_configuration_is_what_the_issue_sized():
    m = _json(CONFIG)
    mixer = flops_lfm2.mixer_param_count
    assert mixer(m, 'conv') == pytest.approx(16.78e6, rel=1e-3)
    assert mixer(m, 'full_attention') == pytest.approx(10.49e6, rel=1e-3)
    assert 32 * flops_lfm2.expert_param_count(m) + 2048 * 32 + 32 == \
        pytest.approx(352.4e6, rel=1e-3)
    assert flops_lfm2.param_count(m) == pytest.approx(2458e6, rel=1e-3)
    assert 4 * flops_lfm2.param_count(m) == pytest.approx(9.83e9, rel=1e-3)
    whole = dict(m, num_hidden_layers=24,
                 layer_types=m['reduced_from']['layer_types'])
    assert flops_lfm2.param_count(whole) == pytest.approx(8.34e9, rel=1e-3)
    # a block of 32 tokens: K/V of 2 layers and 8 heads, and 6 tails
    assert 32 * flops_lfm2.kv_bytes_per_token(m) == 262144
    assert flops_lfm2.tail_bytes_per_block(m) == 98304
    assert 4096 * (262144 + 98304) == pytest.approx(1.476e9, rel=1e-3)
    # 64 rows x 4 of 32 touch every expert; a step streams ~12.2 GB
    assert flops_lfm2.expected_experts_touched(m, 64) == pytest.approx(
        32.0, abs=0.01)
    assert flops_lfm2.decode_bytes_per_step(m, 64 * 4600, 64) == \
        pytest.approx(12.25e9, rel=5e-3)
    # the kernel reads a row once for its 4 query heads: 4 FLOP a byte
    assert flops_lfm2.paged_decode_attention_bytes(m, 1) == 4096
    assert flops_lfm2.paged_decode_attention_flops(m, 1) == 4 * 32 * 64
    cfg = lfm2.lm_config(m, 5120, False)
    assert (cfg.n_head, cfg.n_kv_head, cfg.head_dim, cfg.kv_width,
            cfg.attn_width) == (32, 8, 64, 512, 2048)
    assert cfg.layer_types == ('conv', 'conv', 'attention', 'conv') * 2
    assert (cfg.n_attn_layers, cfg.n_conv_layers, cfg.conv_kernel) == \
        (2, 6, 3)
    assert (cfg.n_experts, cfg.experts_held, cfg.experts_per_token,
            cfg.expert_width, cfg.n_shared_experts, cfg.n_dense_layers,
            cfg.d_ff) == (32, (0, 32), 4, 1792, 0, 2, 7168)
    assert (cfg.moe_score, cfg.routed_scale, cfg.norm_topk_prob,
            cfg.router_eps, cfg.qk_norm, cfg.tie_embeddings) == \
        ('sigmoid', 1.0, True, 1e-6, 'head', True)
    assert not cfg.bias and cfg.rope_theta == 1e6 and cfg.rms_eps == 1e-5
    from paddle_tpu.models.transformer import kv_cache_shapes
    assert kv_cache_shapes(cfg, 4096, 32) == {
        'gen_kv_k': (4096, 2, 32, 512), 'gen_kv_v': (4096, 2, 32, 512),
        'gen_conv_tail': (4096, 6, 2, 2048)}
    with pytest.raises(ValueError):
        lfm2.lm_config(m, 5120, True)                  # served only
    with pytest.raises(ValueError):
        lfm2.lm_config(dict(m, conv_bias=True), 5120, False)
    with pytest.raises(ValueError):
        lfm2.lm_config(dict(m, tie_word_embeddings=False), 5120, False)


def test_init_params_is_seeded_and_gives_taps_and_bias_a_spread():
    m = _json(TOY_CONFIG)
    a, b = lfm2.init_params(m, 3000000001), lfm2.init_params(m, 3000000001)
    c = lfm2.init_params(m, 5)
    assert sorted(a) == sorted(lfm2.param_shapes(m))
    assert 'lm_head.w' not in a
    for name, shape in lfm2.param_shapes(m).items():
        assert tuple(a[name].shape) == tuple(shape)
        np.testing.assert_array_equal(np.asarray(a[name]),
                                      np.asarray(b[name]))
    assert np.abs(np.asarray(a['tok_emb.w'])
                  - np.asarray(c['tok_emb.w'])).max() > 0
    assert np.all(np.asarray(a['layer_2.attn.q_norm.w']) == 1.0)
    assert 0.002 < np.asarray(a['layer_2.moe.router.bias']).std() < 0.03
    assert np.asarray(a['layer_0.conv.w']).std() == pytest.approx(0.3,
                                                                  rel=0.3)
    assert np.asarray(a['layer_0.conv.in.w']).std() == pytest.approx(
        0.02, rel=0.2)


# ---- the readers 

def _reader(name):
    return _load(os.path.join(ROOT, 'benchmark', 'layer_metrics',
                              name + '.py'), 'reader_' + name.replace(
                                  '.', '_'))


M = _json(CONFIG)
PEAKS = {'hbm_bytes_per_s': 819e9, 'bf16_flops_per_s': 197e12}
# a window of 100 decode steps of 6 expert layers, 64 rows x 4 a
# layer-step, all 32 experts touched a layer-step; 64 x 4600 live
# positions x 2 attention layers; 25 admissions of ~4 384 tokens of which
# 4 096 were shared
COUNTERS = {'moe_layer_steps_total': 600, 'moe_assignments_total': 153600,
            'moe_experts_touched_total': 19200,
            'moe_max_expert_rows_total': 9600,
            'kv_tokens_read_total': 100 * 64 * 4600 * 2,
            'prefill_prompt_tokens_total': 25 * 4384,
            'kv_prefix_tokens_saved_total': 25 * 4096}
NEW = ('paged_decode_attention_roofline', 'lfm2_moe_ffn_roofline',
       'prefix_hit_token_share')


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
    olmoe = _json(os.path.join(ROOT, 'benchmark', 'configs',
                               'olmoe-1b-7b-0125-l6.json'))
    old = {k: v for k, v in COUNTERS.items()
           if k not in ('kv_tokens_read_total',
                        'prefill_prompt_tokens_total')}
    both = {'mosaic:ragged-dot-none': 1.0,
            'mosaic:paged_decode_attention': 0.2}
    for facts in ({}, {'counters': {}, 'config': M},
                  {'counters': {}, 'config': {'d_model': 8}, 'trace': None},
                  dict(_traced(**both), counters=old, config=olmoe),
                  dict(_traced(**both), counters={})):
        assert read(facts) is None
    if name != 'prefix_hit_token_share':
        # another family's configuration with every counter there
        assert read(dict(_traced(**both), config={'hidden_size': 8})) is None
        assert read(_traced(fusion=0.5)) is None    # no such operation
        assert read(dict(_traced(), trace=None)) is None


def test_prefix_hit_token_share_on_made_up_facts():
    read = _reader('prefix_hit_token_share').read
    assert read({'counters': COUNTERS}) == pytest.approx(
        100.0 * 4096 / 4384)
    # hits turned into misses: the counter of saved tokens does not move
    assert read({'counters': {'prefill_prompt_tokens_total': 100}}) == 0.0


def test_paged_decode_attention_roofline_on_made_up_facts():
    read = _reader('paged_decode_attention_roofline').read
    need = 100 * 64 * 4600 * 2 * 2 * 512 * 4
    facts = _traced(**{'mosaic:paged_decode_attention': 0.5,
                       'mosaic:ragged-dot-none': 1.0, 'fusion': 0.4})
    # the bytes need need / 4 s / peak of every second; the kernel runs in
    # 0.5 / 2 of every second
    assert read(facts) == pytest.approx(
        100.0 * (need / 4.0 / 819e9) / (0.5 / 2.0))
    assert 0 < read(facts) < 100.0
    # the FLOP share the docstring gives beside it stays far under 100
    flops = flops_lfm2.paged_decode_attention_flops(M, 100 * 64 * 4600 * 2)
    assert 100.0 * (flops / 4.0 / 197e12) / (0.5 / 2.0) < 5.0


def test_lfm2_moe_ffn_roofline_on_made_up_facts():
    read = _reader('lfm2_moe_ffn_roofline').read
    need = flops_lfm2.grouped_matmul_bytes(M, 19200, 153600)
    # 19 200 touched experts x 44 MB is what counts; activations ~0.5 %
    assert need == pytest.approx(19200 * 3 * 2048 * 1792 * 4, rel=0.01)
    facts = _traced(**{'mosaic:ragged-dot-none': 1.6,
                       'mosaic:ragged-dot-metadata': 0.1, 'fusion': 0.5})
    assert read(facts) == pytest.approx(
        100.0 * (need / 4.0 / 819e9) / (1.7 / 2.0))
    assert 0 < read(facts) < 100.0
    # OLMoE's reader on this configuration's keys counts an expert at the
    # dense layers' width: four times the bytes (why the cell is not on
    # moe_ffn_hbm_share's list)
    from benchmark import flops_moe
    assert flops_moe.grouped_matmul_bytes(M, 19200, 0) == pytest.approx(
        4 * flops_lfm2.grouped_matmul_bytes(M, 19200, 0))


@pytest.mark.parametrize('name,op,counters', [
    ('paged_decode_attention_roofline', 'mosaic:paged_decode_attention',
     lambda s: {'kv_tokens_read_total': int(819e9 * s / 4096)}),
    ('lfm2_moe_ffn_roofline', 'mosaic:ragged-dot-none',
     lambda s: {'moe_experts_touched_total':
                int(819e9 * s / (3 * 2048 * 1792 * 4)),
                'moe_assignments_total': 0})])
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

def test_lfm2_control_main_at_toy_width(capsys):
    from benchmark.reference import lfm2_control
    rc = lfm2_control.main([
        TOY_CONFIG, os.path.join(HERE, 'traffic', 'toy-serve-lfm2.json'),
        '5', '3000000009'])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith('{')]
    assert rc == 0 and len(lines) == 4
    for out in lines:
        assert out['rows'] == min(25, 40 - out['prompt_len'] + 1)
        assert out['logits_vs_ref'][1] < 1e-4
        assert out['resumed_logits_vs_ref'][1] < 1e-4
        assert out['resumed_tokens_equal_whole'] is True
        assert out['refused_by_logits_rms'] is False
        assert set(out['controls']) == {
            'bfloat16', 'zero-tail-resume', 'kv-head-modulo',
            'no-head-norm', 'top-1', 'bias-in-weights', 'untied-head'}
        assert out['controls']['zero-tail-resume']['logits_vs_ref'][1] > 5e-4
        # at the first row behind the prefix the program is the
        # reference's and a zero tail is far off
        assert out['row_behind_prefix_vs_ref'][1] < 1e-4
        assert out['row_behind_prefix_zero_tail_vs_ref'][1] > 4e-3
