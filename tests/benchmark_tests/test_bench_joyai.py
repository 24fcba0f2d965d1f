"""The JoyAI-LLM-Flash configuration's benchmark files (ISSUE 32): a toy cell
with the new builder through run.py end to end on the CPU (its own toy
manifest; BENCHMARK.json lists the three per-layer metrics it brought
since PR 34), the manifest's entry and the published
file against the catalog's row, flops_joyai's formulae against a count of
param_shapes, the three new readers on made-up facts, and the comparison
script's main() at toy width."""
import json
import os

import numpy as np
import pytest

from benchmark import flops_joyai
from benchmark.models import joyai

from test_bench_olmoe import _last_json, _load, run_on_cpu   # noqa: F401
from test_bench_run import MANIFEST, by_name, check_config_entry

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TOY_MANIFEST = os.path.join(HERE, 'fixtures', 'BENCHMARK.toy.joyai.json')
TOY_CONFIG = os.path.join(HERE, 'configs', 'toy-joyai.json')
CONFIG = os.path.join(ROOT, 'benchmark', 'configs',
                      'joyai-llm-flash-ep4.json')
CELL = 'joyai-serve-longchat64'
CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'


def _json(path):
    with open(path) as f:
        return json.load(f)


# ---- the toy cell through run.py 

def test_end_to_end_line(run_on_cpu, capsys):              # noqa: F811
    rc = run_on_cpu.main(['--workload', 'toy-serve-joyai', '--seed',
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
    rc = run_on_cpu.main(['--workload', 'toy-serve-joyai', '--seed', '7',
                          '--seconds', '0.7', '--trace', '1'],
                         manifest_path=TOY_MANIFEST)
    out, lines = _last_json(capsys)
    assert rc == 0 and out['correct'] is True
    # on the CPU no operation of the trace is a Mosaic kernel and
    # peak_hbm_gb.serve has nothing to read: those readers return nothing
    assert set(out['metrics']) == {
        'decode_step_ms', 'decode_hbm_share', 'device_idle_share.serve',
        'ttft_p95_unbounded_ms', 'ttft_mean_unbounded_ms',
        'decode_host_gap_ms.deliver', 'decode_sampled_step_share',
        'moe_held_assignment_share'}
    # experts 4..7 of 16, four a row: a quarter under even routing
    assert 2.0 < out['metrics']['moe_held_assignment_share']['value'] < 75.0
    assert 0 < out['metrics']['decode_hbm_share']['value'] < 100
    facts = json.loads([ln for ln in lines
                        if ln.startswith('facts: ')][-1][len('facts: '):])
    assert facts['decode_bytes_per_step'] > 0


# ---- the manifest and the published file 

def check_joyai_entry(manifest):
    """The configuration, its cell and the metrics that list the cell,
    each found by name: where they stand in their lists is
    test_bench_manifest.py's to hold (appended, never put in)."""
    conf = by_name(manifest['configs'], 'joyai-llm-flash-ep4')
    check_config_entry(conf, manifest)
    assert conf['reduced'] == ['num_hidden_layers', 'n_routed_experts']
    cell = by_name(manifest['workloads'], CELL)
    assert cell == dict(cell, name=CELL, config=conf['name'],
                        traffic='longchat64-closed', chips=1)
    # listed under every serve metric whose reader asks nothing of the
    # configuration and under the three that read its own keys and
    # counters, and under no metric that reads OLMoE's key names
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
        'mla_decode_attention_hbm_share', 'moe_held_assignment_share',
        'moe_held_ffn_hbm_share'}


def test_config_entry_admits_the_new_entry():
    check_joyai_entry(MANIFEST)


def test_the_published_file_keeps_every_number_of_the_catalogs_row():
    if not os.path.isfile(CATALOG):
        pytest.skip('no catalog on this machine')
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f)
                if r['name'] == 'JoyAI-LLM-Flash']
    m = _json(CONFIG)
    differs = {k for k, v in row['config'].items() if m.get(k, '?') != v}
    assert differs == set(m['reduced']) == set(m['reduced_from'])
    assert all(m['reduced_from'][k] == row['config'][k] for k in differs)
    assert m['source'].startswith(row['source_url'])
    assert m['num_hidden_layers'] >= 5 and m['n_routed_experts'] == 64
    assert m['deployment'].strip() and m['changed'] and 'assumed' in m


def test_the_traffic_is_the_issues_letter_for_letter():
    tr = _json(os.path.join(ROOT, 'benchmark', 'traffic',
                            'longchat64-closed.json'))
    assert tr['arrival'] == {'kind': 'closed', 'clients': 64,
                             'stagger_s': 0.1}
    assert tr['prompt_len'] == {'dist': 'lognormal', 'median': 1024,
                                'sigma': 0.6, 'min': 256, 'max': 2048}
    assert tr['output_len'] == {'dist': 'lognormal', 'median': 384,
                                'sigma': 0.5, 'min': 128, 'max': 768}
    assert tr['engine'] == {'paged': True, 'slots': 64, 'block_size': 16,
                            'max_len': 2816,
                            'prompt_buckets': [512, 1024, 2048],
                            'num_blocks': 8192}
    assert (tr['sampling'], tr['shared_prefix_len'],
            tr['check_new_tokens']) == ('greedy', 0, 8)
    assert tr['pool_size'] % 64 == 0
    assert tr['prompt_len']['max'] + tr['output_len']['max'] \
        <= tr['engine']['max_len']


# ---- flops_joyai against a count of the parameters 

def _count(shapes, pick=lambda name: True):
    return sum(int(np.prod(s)) for n, s in shapes.items() if pick(n))


@pytest.mark.parametrize('path', [CONFIG, TOY_CONFIG],
                         ids=['joyai-llm-flash-ep4', 'toy-joyai'])
def test_flops_joyai_counts_what_param_shapes_lists(path):
    m = _json(path)
    shapes = joyai.param_shapes(m)
    n, dense = m['num_hidden_layers'], m['first_k_dense_replace']
    assert flops_joyai.param_count(m) == _count(shapes)
    for i in (0, n - 1):
        assert flops_joyai.layer_param_count(m, i) == _count(
            shapes, lambda k: k.startswith('layer_%d.' % i))
    assert flops_joyai.attention_param_count(m) == _count(
        shapes, lambda k: k.startswith('layer_0.attn.'))
    routed = lambda k: '.moe.' in k and 'router' not in k \
        and 'shared' not in k                               # noqa: E731
    assert flops_joyai.expert_param_count(m) * m['n_routed_experts'] \
        * (n - dense) == _count(shapes, routed)
    assert flops_joyai.kv_bytes_per_token(m) == n * 4 * (
        m['kv_lora_rank'] + m['qk_rope_head_dim'])
    cfg = joyai.lm_config(m, 64, False)
    assert flops_joyai.pool_bytes_per_token(m) == n * 4 * cfg.kv_width
    # one live row reads k x (held / all) experts a layer in expectation;
    # very many rows read every weight but the embedding table
    one = flops_joyai.decode_bytes_per_step(m, 0, 1)
    rest = _count(shapes, lambda k: not routed(k) and k != 'tok_emb.w')
    per_expert = flops_joyai.expert_param_count(m)
    share = m['n_routed_experts'] / flops_joyai.router_width(m)
    assert one == pytest.approx(4 * (
        rest + (n - dense) * m['num_experts_per_tok'] * share * per_expert
        + m['hidden_size']))
    many = flops_joyai.decode_bytes_per_step(m, 0, 100000)
    assert many == pytest.approx(
        4 * (_count(shapes) - _count(shapes, lambda k: k == 'tok_emb.w')
             + 100000 * m['hidden_size']), rel=1e-6)
    assert flops_joyai.decode_bytes_per_step(m, 100, 1) - one == \
        100 * flops_joyai.kv_bytes_per_token(m)


def test_the_published_configuration_is_what_the_issue_sized():
    m = _json(CONFIG)
    # attention 26.35 M; layer 0 70.4 M; an expert layer with 64 of 256
    # experts 333.6 M (302.0 M routed), with all 256 it would be 1 239.6 M
    assert flops_joyai.attention_param_count(m) == pytest.approx(26.35e6,
                                                                 rel=1e-3)
    assert flops_joyai.layer_param_count(m, 0) == pytest.approx(70.4e6,
                                                                rel=1e-3)
    assert flops_joyai.layer_param_count(m, 1) == pytest.approx(333.6e6,
                                                                rel=1e-3)
    assert 64 * flops_joyai.expert_param_count(m) == pytest.approx(
        302.0e6, rel=1e-3)
    whole = dict(m, n_routed_experts=256, reduced_from={})
    assert flops_joyai.layer_param_count(whole, 1) == pytest.approx(
        1239.6e6, rel=1e-3)
    n = m['num_hidden_layers']
    assert 4 * flops_joyai.param_count(m) == pytest.approx(
        2.118e9 + 0.2816e9 + (n - 1) * 1.3343e9, rel=1e-3)
    # 64 rows x 8 of 256: 2 rows a held expert, ~87 % of them touched; a
    # per-head cache would be 17.8 times the latent one
    assert flops_joyai.expected_experts_touched(m, 64) == pytest.approx(
        55.6, abs=0.1)
    assert flops_joyai.kv_bytes_per_token(m) == n * 2304
    assert 32 * (192 + 128) * 4 / 2304 == pytest.approx(17.8, abs=0.05)
    # 32 heads x (576 + 512) x 2 operations a latent row read
    assert flops_joyai.mla_decode_flops(m, 1) == 2 * 32 * 1088
    cfg = joyai.lm_config(m, 2816, False)
    assert (cfg.attention, cfg.norm, cfg.position, cfg.ffn) == \
        ('mla', 'rms_norm', 'rope', 'moe')
    assert (cfg.n_head, cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_dim,
            cfg.qk_rope_dim, cfg.v_head_dim) == (32, 1536, 512, 128, 64, 128)
    assert (cfg.kv_width, cfg.attn_width, cfg.rope_interleave) == \
        (640, 4096, True)
    assert (cfg.n_experts, cfg.experts_held, cfg.experts_per_token,
            cfg.expert_width, cfg.n_shared_experts, cfg.n_dense_layers) == \
        (256, (0, 64), 8, 768, 1, 1)
    assert (cfg.moe_score, cfg.routed_scale, cfg.norm_topk_prob,
            cfg.d_ff) == ('sigmoid', 2.5, True, 7168)
    assert not cfg.bias and cfg.rope_theta == 32e6 and cfg.rms_eps == 1e-6
    with pytest.raises(ValueError):
        joyai.lm_config(m, 2816, True)                 # served only
    with pytest.raises(ValueError):
        joyai.lm_config(dict(m, rope_scaling={'type': 'yarn'}), 2816, False)


def test_init_params_is_seeded_and_gives_the_bias_a_spread():
    m = _json(TOY_CONFIG)
    a, b = joyai.init_params(m, 3000000001), joyai.init_params(m, 3000000001)
    c = joyai.init_params(m, 5)
    assert sorted(a) == sorted(joyai.param_shapes(m))
    for name, shape in joyai.param_shapes(m).items():
        assert tuple(a[name].shape) == tuple(shape)
        np.testing.assert_array_equal(np.asarray(a[name]),
                                      np.asarray(b[name]))
    assert np.abs(np.asarray(a['lm_head.w'])
                  - np.asarray(c['lm_head.w'])).max() > 0
    assert np.all(np.asarray(a['layer_1.ln1.w']) == 1.0)
    bias = np.asarray(a['layer_1.moe.router.bias'])
    assert 0.002 < bias.std() < 0.03
    assert np.asarray(a['layer_0.ffn.gate.w']).std() == pytest.approx(
        0.02, rel=0.2)


# ---- the readers 

def _reader(name):
    return _load(os.path.join(ROOT, 'benchmark', 'layer_metrics',
                              name + '.py'), 'reader_' + name.replace(
                                  '.', '_'))


M = _json(CONFIG)
PEAKS = {'hbm_bytes_per_s': 819e9, 'bf16_flops_per_s': 197e12}
# a window of 100 decode steps of 6 expert layers, 64 rows x 8 a
# layer-step of which a quarter is held, 56 of the 64 held experts touched
# a layer-step, the busiest with 6 rows; 90 000 live positions x 7 layers
COUNTERS = {'moe_layer_steps_total': 600, 'moe_assignments_total': 307200,
            'moe_held_assignments_total': 76800,
            'moe_experts_touched_total': 33600,
            'moe_max_expert_rows_total': 3600,
            'kv_latent_tokens_read_total': 100 * 90000 * 7}
NEW = ('mla_decode_attention_hbm_share', 'moe_held_assignment_share',
       'moe_held_ffn_hbm_share')


def _traced(**ops):
    return {'counters': COUNTERS, 'config': M, 'peaks': PEAKS,
            'window_s': 4.0, 'trace': {'window_s': 2.0, 'busy_s': 1.8,
                                       'op_seconds': ops}}


@pytest.mark.parametrize('name', NEW)
def test_a_new_reader_reads_nothing_where_there_is_nothing(name):
    """The parent commit's program, another configuration, an untraced or
    a CPU run: nothing to read, nothing raised."""
    read = _reader(name).read
    olmoe = _json(os.path.join(ROOT, 'benchmark', 'configs',
                               'olmoe-1b-7b-0125-l6.json'))
    old = {k: v for k, v in COUNTERS.items()
           if k not in ('moe_held_assignments_total',
                        'kv_latent_tokens_read_total')}
    for facts in ({}, {'counters': {}, 'config': M},
                  {'counters': {}, 'config': {'d_model': 8}, 'trace': None},
                  dict(_traced(**{'mosaic:ragged-dot-none': 1.0,
                                  'mosaic:paged_decode_attention': 0.2}),
                       counters=old, config=olmoe),
                  dict(_traced(fusion=0.5), counters=old)):
        assert read(facts) is None
    if name != 'moe_held_assignment_share':
        assert read(_traced(fusion=0.5)) is None    # no such operation
        assert read(dict(_traced(), trace=None)) is None


def test_moe_held_assignment_share_on_made_up_facts():
    read = _reader('moe_held_assignment_share').read
    assert read({'counters': COUNTERS}) == pytest.approx(25.0)
    assert read({'counters': dict(COUNTERS,
                                  moe_held_assignments_total=0)}) == 0.0
    every = dict(COUNTERS, moe_held_assignments_total=307200)
    assert read({'counters': every}) == pytest.approx(100.0)


def test_mla_decode_attention_hbm_share_on_made_up_facts():
    read = _reader('mla_decode_attention_hbm_share').read
    need = 100 * 90000 * 7 * 576 * 4
    facts = _traced(**{'mosaic:mla_paged_decode_attention': 0.3,
                       'mosaic:ragged-dot-none': 1.0, 'fusion': 0.4})
    # the bytes need need / 4 s / peak of every second; the kernel runs in
    # 0.3 / 2 of every second
    assert read(facts) == pytest.approx(
        100.0 * (need / 4.0 / 819e9) / (0.3 / 2.0))
    assert 0 < read(facts) < 105.0
    # the FLOP share the docstring gives beside it stays far under 100
    flops = flops_joyai.mla_decode_flops(M, 100 * 90000 * 7)
    assert 100.0 * (flops / 4.0 / 197e12) / (0.3 / 2.0) < 20.0


def test_moe_held_ffn_hbm_share_on_made_up_facts():
    read = _reader('moe_held_ffn_hbm_share').read
    need = flops_joyai.grouped_matmul_bytes(M, 33600, 76800)
    # 33 600 touched experts x 18.9 MB is what counts; activations ~0.3 %
    assert need == pytest.approx(33600 * 3 * 2048 * 768 * 4, rel=0.01)
    facts = _traced(**{'mosaic:ragged-dot-none': 1.6,
                       'mosaic:ragged-dot-metadata': 0.1, 'fusion': 0.5})
    assert read(facts) == pytest.approx(
        100.0 * (need / 4.0 / 819e9) / (1.7 / 2.0))
    assert 0 < read(facts) < 105.0


# ---- the comparison script, as the chip runs it -----------------------------

def test_joyai_control_main_at_toy_width(capsys):
    from benchmark.reference import joyai_control
    rc = joyai_control.main([
        TOY_CONFIG, os.path.join(HERE, 'traffic', 'toy-serve-joyai.json'),
        '5', '3000000009'])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith('{')]
    assert rc == 0 and len(lines) == 4
    for out in lines:
        assert out['rows'] == 24 - out['prompt_len'] + 1   # max_len 24
        assert out['routing_rows_not_ref_top_k'] == 0.0
        assert out['logits_vs_ref_given_routing'][1] < 1e-4
        assert out['logits_vs_ref_own_routing'][1] < 1e-4
        assert out['refused_by_logits_rms'] is False
        assert set(out['controls']) == {
            'bfloat16', 'top-3', 'not-renormalised', 'unscaled',
            'rotate-half', 'bias-in-weights'}
