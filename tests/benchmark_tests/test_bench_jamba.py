"""The AI21-Jamba2-3B configuration's benchmark files (ISSUE 43): a toy cell
with the new builder through run.py end to end on the CPU (its own toy
manifest; prompts chunked over the widest bucket resume from the slot's
state row), the manifest's entries and the published file against the
catalog's row, the traffic letter for letter, flops_jamba's formulae
against a count of param_shapes and against the issue's table, the three
new readers and the accepted readers the cell is listed under on made-up
facts of this configuration (no roofline over 100 on a trace that spends
its whole window in the kernel at peak), and the comparison script's
main() at toy width."""
import json
import os

import numpy as np
import pytest

from benchmark import flops_jamba, traffic_gen
from benchmark.models import jamba

from test_bench_olmoe import _last_json, _load, run_on_cpu   # noqa: F401
from test_bench_run import MANIFEST, by_name, check_config_entry

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TOY_MANIFEST = os.path.join(HERE, 'fixtures', 'BENCHMARK.toy.jamba.json')
TOY_CONFIG = os.path.join(HERE, 'configs', 'toy-jamba.json')
CONFIG = os.path.join(ROOT, 'benchmark', 'configs', 'ai21-jamba2-3b.json')
TRAFFIC = os.path.join(ROOT, 'benchmark', 'traffic', 'reason128-closed.json')
CELL = 'jamba2-serve-reason128'
CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'
NEW = ('ssm_decode_state_roofline', 'ssm_prefill_scan_roofline',
       'ssm_state_step_share')
ROW = 19 * 5120 * 4            # one layer's state and tail of one slot


def _json(path):
    with open(path) as f:
        return json.load(f)


# ---- the toy cell through run.py --------------------------------------------

def test_end_to_end_line(run_on_cpu, capsys):              # noqa: F811
    rc = run_on_cpu.main(['--workload', 'toy-serve-jamba', '--seed',
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
    rc = run_on_cpu.main(['--workload', 'toy-serve-jamba', '--seed', '7',
                          '--seconds', '0.7', '--trace', '1'],
                         manifest_path=TOY_MANIFEST)
    out, lines = _last_json(capsys)
    assert rc == 0 and out['correct'] is True
    # on the CPU no operation of the trace is a Mosaic kernel and
    # peak_hbm_gb.serve has nothing to read: the two rooflines and the
    # peak return nothing
    assert set(out['metrics']) == {
        'decode_step_ms', 'decode_hbm_share', 'device_idle_share.serve',
        'ssm_state_step_share'}
    # the toy's weights are small beside three slots' state: the share is
    # a share all the same
    assert 0 < out['metrics']['ssm_state_step_share']['value'] < 100
    assert 0 < out['metrics']['decode_hbm_share']['value'] < 100
    facts = json.loads([ln for ln in lines
                        if ln.startswith('facts: ')][-1][len('facts: '):])
    assert facts['decode_bytes_per_step'] > 0


# ---- the manifest and the published file ------------------------------------

def check_jamba_entry(manifest):
    """The configuration, its cell and the metrics that list the cell,
    each found by name: where they stand in their lists is
    test_bench_manifest.py's to hold (appended, never put in)."""
    conf = by_name(manifest['configs'], 'ai21-jamba2-3b')
    check_config_entry(conf, manifest)
    assert conf['reduced'] == []
    assert conf['source'] == 'https://huggingface.co/ai21labs/' \
        'AI21-Jamba2-3B/blob/main/config.json'
    cell = by_name(manifest['workloads'], CELL)
    assert cell == dict(cell, name=CELL, config=conf['name'],
                        traffic='reason128-closed', chips=1)
    listed = {x['name'] for x in manifest['end_to_end']
              + manifest['per_layer'] if CELL in x.get('workloads', ())}
    # both end-to-end metrics, every per-layer metric whose reader asks
    # nothing of the configuration that it lacks, and its own three. At
    # least: a later PR may append
    assert listed >= {
        'serve_tokens_per_s', 'itl_p95_ms', 'decode_step_ms',
        'decode_hbm_share', 'decode_host_gap_ms',
        'decode_host_gap_ms.admit', 'decode_host_gap_ms.feed',
        'decode_host_gap_ms.dispatch', 'decode_host_gap_ms.deliver',
        'decode_sampled_step_share', 'decode_overlapped_step_share',
        'device_idle_share.serve', 'peak_hbm_gb.serve',
        'ttft_p95_unbounded_ms', 'ttft_mean_unbounded_ms',
        'server_loop_unaccounted_share', 'admission_ms',
        'admission_ms.dispatch', 'admission_ms.drain', 'admission_ms.fetch',
        'token_gap_ms.admission', 'token_gap_ms.plain',
        'token_gap_admission_share'} | set(NEW)
    # NOT under a reader of experts or of another family's kernel, and not
    # under paged_decode_attention_roofline: test_bench_lfm2.py holds its
    # list with `==` (PERF.md section 7 (b) has the repair)
    assert not {n for n in listed if n.startswith(('moe_', 'mla_', 'kv_'))}
    assert not listed & {'paged_decode_attention_roofline',
                         'window_decode_attention_roofline',
                         'lfm2_moe_ffn_roofline', 'kexaone_moe_ffn_roofline',
                         'prefix_hit_token_share', 'ttft_p95_ms'}
    by = {x['name']: x for x in manifest['per_layer']}
    for name in NEW:
        assert CELL in by[name]['workloads']
    assert by['ssm_prefill_scan_roofline']['moves'] == 'itl_p95_ms'
    assert by['ssm_decode_state_roofline']['moves'] == \
        by['ssm_state_step_share']['moves'] == 'serve_tokens_per_s'


def test_config_entry_admits_the_new_entry():
    check_jamba_entry(MANIFEST)


def test_the_published_file_keeps_every_number_of_the_catalogs_row():
    if not os.path.isfile(CATALOG):
        pytest.skip('no catalog on this machine')
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f)
                if r['name'] == 'AI21-Jamba2-3B']
    m = _json(CONFIG)
    # nothing is cut: every key of the row stands as it is
    assert {k for k, v in row['config'].items() if m.get(k, '?') != v} \
        == set(m['reduced']) == set()
    assert 'reduced_from' not in m
    assert m['source'].startswith(row['source_url'])
    assert m['builder'] == 'jamba'
    # every width of the row
    assert (m['hidden_size'], m['num_hidden_layers'],
            m['num_attention_heads'], m['num_key_value_heads'],
            m['intermediate_size'], m['mamba_expand'], m['mamba_d_state'],
            m['mamba_d_conv'], m['mamba_dt_rank'], m['mamba_conv_bias'],
            m['rms_norm_eps'], m['vocab_size'], m['tie_word_embeddings'],
            m['attn_layer_period'], m['attn_layer_offset']) == \
        (2560, 28, 20, 1, 8192, 2, 16, 4, 160, True, 1e-6, 65536, True, 14, 7)
    kinds = flops_jamba.layer_types(m)
    assert [i for i, k in enumerate(kinds) if k == 'attention'] == [7, 21]
    assert kinds.count('mamba') == 26
    assert m['deployment'].strip() and m['changed']
    assert set(m['assumed']) == {'order_of_the_layer_types', 'head_dim',
                                 'expert_layers'}
    assert 'float32' in m['changed']['serving_dtype']
    assert '3072' in m['changed']['context']


def test_the_traffic_is_the_issues_letter_for_letter():
    tr = _json(TRAFFIC)
    assert tr['kind'] == 'serve'
    assert tr['arrival'] == {'kind': 'closed', 'clients': 128,
                             'stagger_s': 0.1}
    assert tr['prompt_len'] == {'dist': 'lognormal', 'median': 256,
                                'sigma': 0.7, 'min': 32, 'max': 1024}
    assert tr['output_len'] == {'dist': 'lognormal', 'median': 512,
                                'sigma': 0.5, 'min': 128, 'max': 2048}
    assert tr['engine'] == {'paged': True, 'slots': 128, 'block_size': 32,
                            'max_len': 3072,
                            'prompt_buckets': [128, 256, 512],
                            'num_blocks': 8192}
    assert (tr['pool_size'], tr['sampling'], tr['shared_prefix_len'],
            tr['group_size'], tr['check_new_tokens']) \
        == (1024, 'greedy', 0, 0, 8)
    # NOT the issue's 3.0: ssm_prefill_scan_roofline takes its bytes from
    # the window's counters and its time from the trace, and 3 s hold only
    # ~23 prefill dispatches (PERF.md section 6, PR 43's review)
    assert tr['trace_seconds'] == 8.0
    # eight requests a client; the check's longest prompt is two chunks:
    # the state crosses a chunk's edge
    plen = traffic_gen.length_pool(tr['prompt_len'], tr['pool_size'])
    olen = traffic_gen.length_pool(tr['output_len'], tr['pool_size'])
    assert (plen.min(), plen.max()) == (32, 1024)
    assert 512 < plen.max() <= 2 * 512
    assert 280 < plen.mean() < 340 and 520 < olen.mean() < 640
    # one prompt in six or so runs as two chunks, about half take the 512
    # bucket or more
    assert 0.08 < (plen > 512).mean() < 0.25
    assert 0.35 < (plen > 256).mean() < 0.6
    # the longest request fits the table, and the pool what the slots hold
    assert tr['prompt_len']['max'] + tr['output_len']['max'] \
        <= tr['engine']['max_len']
    assert tr['engine']['num_blocks'] * 32 >= 128 * (plen.mean()
                                                     + olen.mean())


# ---- flops_jamba against a count of the parameters --------------------------

def _count(shapes, pick=lambda name: True):
    return sum(int(np.prod(s)) for n, s in shapes.items() if pick(n))


@pytest.mark.parametrize('path', [CONFIG, TOY_CONFIG],
                         ids=['ai21-jamba2-3b', 'toy-jamba'])
def test_flops_jamba_counts_what_param_shapes_lists(path):
    m = _json(path)
    shapes = jamba.param_shapes(m)
    assert flops_jamba.param_count(m) == _count(shapes)
    for i, kind in enumerate(flops_jamba.layer_types(m)):
        assert flops_jamba.layer_param_count(m, i) == _count(
            shapes, lambda k: k.startswith('layer_%d.' % i))
        mixer = '.ssm.' if kind == 'mamba' else '.attn.'
        assert flops_jamba.mixer_param_count(m, kind) == _count(
            shapes, lambda k: k.startswith('layer_%d.' % i) and mixer in k)
    cfg = jamba.lm_config(m, 32, False)
    assert flops_jamba.kv_bytes_per_token(m) == \
        2 * cfg.n_attn_layers * cfg.kv_width * 4
    from paddle_tpu.models import transformer as T
    pools = T.kv_cache_shapes(cfg, 4, 8, 1)
    # one slot's row of the state pool, and the K - 1 rows that count of
    # the 8 its block of the tail pool holds, are what
    # `state_bytes_per_slot` says
    assert pools[T.SSM_TAIL][2] == 8
    assert flops_jamba.state_bytes_per_slot(m) == 4 * (
        int(np.prod(pools[T.SSM_STATE][1:]))
        + int(np.prod(pools[T.SSM_TAIL][1:])) * (m['mamba_d_conv'] - 1) // 8)
    one = flops_jamba.decode_bytes_per_step(m, 0, 1)
    assert one == 4 * _count(shapes) \
        + 2 * flops_jamba.state_bytes_per_slot(m)
    assert flops_jamba.decode_bytes_per_step(m, 100, 1) - one == \
        100 * flops_jamba.kv_bytes_per_token(m)


def test_the_published_configuration_is_what_the_issue_sized():
    m = _json(CONFIG)
    f = flops_jamba
    assert f.param_count(m) == 3029337472
    assert f.mixer_param_count(m, 'mamba') == 41241792
    assert f.mixer_param_count(m, 'attention') == 13762560
    assert 3 * 2560 * 8192 == 62914560
    assert 4 * f.layer_param_count(m, 0) == pytest.approx(0.4166e9, rel=1e-3)
    assert 4 * f.layer_param_count(m, 7) == pytest.approx(0.3067e9, rel=1e-3)
    assert 4 * f.param_count(m) == pytest.approx(12.12e9, rel=1e-3)
    # the state: 10.12 MB a slot whatever the context, 154 times a block
    assert f.state_row_bytes(m) == ROW
    assert f.state_bytes_per_slot(m) == 10117120
    assert f.kv_bytes_per_token(m) == 2048
    assert 32 * f.kv_bytes_per_token(m) == 65536
    assert f.state_bytes_per_slot(m) / 65536.0 == pytest.approx(154.4,
                                                                abs=0.1)
    assert 129 * f.state_bytes_per_slot(m) == pytest.approx(1.305e9,
                                                            rel=1e-3)
    assert 8192 * 65536 == pytest.approx(0.537e9, rel=1e-3)
    # ~14.9 GB a step at 128 rows and ~75 k live tokens, the state ~17 %
    step = f.decode_bytes_per_step(m, 75000, 128)
    assert step == pytest.approx(14.86e9, rel=2e-3)
    assert 2 * 128 * f.state_bytes_per_slot(m) / step == pytest.approx(
        0.174, abs=0.002)
    cfg = jamba.lm_config(m, 3072, False)
    from paddle_tpu.models import transformer as T
    assert T.kv_cache_shapes(cfg, 8192, 32, 128) == {
        'gen_kv_k': (8192, 2, 32, 128), 'gen_kv_v': (8192, 2, 32, 128),
        'gen_ssm_state': (129, 26, 16, 5120),
        'gen_ssm_tail': (129, 26, 8, 5120)}
    assert (cfg.n_head, cfg.n_kv_head, cfg.head_dim, cfg.kv_width,
            cfg.attn_width, cfg.d_ff) == (20, 1, 128, 128, 2560, 8192)
    assert (cfg.n_ssm_layers, cfg.n_attn_layers, cfg.ssm_inner,
            cfg.ssm_state, cfg.ssm_conv, cfg.ssm_dt_rank) == \
        (26, 2, 5120, 16, 4, 160)
    assert [i for i, k in enumerate(cfg.layer_types) if k == 'attention'] \
        == [7, 21]
    assert (cfg.position, cfg.ffn, cfg.norm, cfg.rms_eps, cfg.bias,
            cfg.tie_embeddings, cfg.qk_norm) == \
        ('none', 'gated', 'rms_norm', 1e-6, False, True, False)
    assert not any(cfg.rotates(i) for i in range(28))
    with pytest.raises(ValueError):
        jamba.lm_config(m, 3072, True)                  # served only
    for key, other in (('hidden_act', 'gelu'), ('num_experts', 16),
                       ('num_experts_per_tok', 2),
                       ('mamba_conv_bias', False),
                       ('mamba_proj_bias', True),
                       ('tie_word_embeddings', False),
                       ('sliding_window', 4096)):
        with pytest.raises(ValueError, match=key):
            jamba.lm_config(dict(m, **{key: other}), 3072, False)


def test_init_params_is_seeded_and_takes_mambas_initialisation():
    m = _json(TOY_CONFIG)
    a = jamba.init_params(m, 3000000001)
    b = jamba.init_params(m, 3000000001)
    c = jamba.init_params(m, 5)
    assert sorted(a) == sorted(jamba.param_shapes(m))
    for name, shape in jamba.param_shapes(m).items():
        assert tuple(a[name].shape) == tuple(shape)
        np.testing.assert_array_equal(np.asarray(a[name]),
                                      np.asarray(b[name]))
    assert np.abs(np.asarray(a['tok_emb.w'])
                  - np.asarray(c['tok_emb.w'])).max() > 0
    ln = np.asarray(a['layer_3.ln1.w'])
    assert abs(ln.mean() - 1.0) < 0.05 and 0.05 < ln.std() < 0.2
    assert np.asarray(a['layer_2.attn.qkv.w']).std() == pytest.approx(
        0.02, rel=0.2)
    assert 0.2 < np.asarray(a['layer_0.ssm.conv.w']).std() < 0.4
    assert 0.05 < np.asarray(a['layer_0.ssm.conv.b']).std() < 0.15
    # the recurrence: A = -(1 .. N) a channel, D = 1, the step between
    # 1e-3 and 1e-1 at a zero input, log-uniform
    np.testing.assert_allclose(
        np.exp(np.asarray(a['layer_1.ssm.A_log'])),
        np.broadcast_to(np.arange(1, 17)[:, None], (16, 128)), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(a['layer_1.ssm.D']), 1.0)
    dt = np.logaddexp(0, np.asarray(a['layer_1.ssm.dt.b'], 'float64'))
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 1e-1 * 1.001
    assert 0.8 < np.log(dt).std() < 1.8
    # the slowest channel keeps more than 0.99 of its state a position
    assert np.exp(-dt.min()) > 0.99


# ---- the readers ------------------------------------------------------------

def _reader(name):
    return _load(os.path.join(ROOT, 'benchmark', 'layer_metrics',
                              name + '.py'), 'reader_' + name.replace(
                                  '.', '_'))


M = _json(CONFIG)
PEAKS = {'hbm_bytes_per_s': 819e9, 'bf16_flops_per_s': 197e12}
# a window of 200 decode steps of 26 Mamba layers at 120 active rows; 30
# admissions of which 5 ran as two chunks, 9 000 real prompt rows
COUNTERS = {'ssm_state_rows_updated_total': 200 * 120 * 26,
            'ssm_prefill_rows_total': 9000 * 26,
            'ssm_state_resumes_total': 5,
            'kv_tokens_read_total': 200 * 120 * 600 * 2}
HIST = {'prefill_seconds': (30, 1.2), 'decode_step_seconds': (200, 4.4)}
ROOFLINES = ('ssm_decode_state_roofline', 'ssm_prefill_scan_roofline')


def _traced(**ops):
    return {'counters': COUNTERS, 'config': M, 'peaks': PEAKS,
            'histograms': HIST, 'window_s': 5.0, 'decode_steps': 200,
            'decode_bytes_per_step': flops_jamba.decode_bytes_per_step(
                M, 72000, 120),
            'trace': {'window_s': 2.0, 'busy_s': 1.9, 'op_seconds': ops}}


@pytest.mark.parametrize('name', NEW)
def test_a_new_reader_reads_nothing_where_there_is_nothing(name):
    """The parent commit's program (no such counter, no such operation),
    another configuration, an untraced or a CPU run: nothing to read,
    nothing raised."""
    read = _reader(name).read
    lfm2 = _json(os.path.join(ROOT, 'benchmark', 'configs',
                              'lfm2-8b-a1b-l8.json'))
    both = {'mosaic:ssm_decode_update': 0.4, 'mosaic:ssm_prefill_scan': 0.2}
    for facts in ({}, {'counters': {}, 'config': M},
                  {'counters': {}, 'config': {'d_model': 8}, 'trace': None},
                  dict(_traced(**both), config=lfm2),
                  dict(_traced(**both), config={'hidden_size': 8}),
                  dict(_traced(**both), counters={}),
                  dict(_traced(**both), counters={
                      'kv_tokens_read_total': 5})):
        assert read(facts) is None
    if name in ROOFLINES:
        assert read(_traced(fusion=0.5)) is None    # no such operation
        assert read(dict(_traced(**both), trace=None)) is None
        # the one kernel is not the other
        other = {'mosaic:ssm_prefill_scan': 0.2} \
            if name == 'ssm_decode_state_roofline' \
            else {'mosaic:ssm_decode_update': 0.2}
        assert read(_traced(**other)) is None
    else:
        assert read(dict(_traced(), decode_bytes_per_step=None)) is None


def test_ssm_state_step_share_on_made_up_facts():
    read = _reader('ssm_state_step_share').read
    need = flops_jamba.decode_bytes_per_step(M, 72000, 120)
    assert read(_traced()) == pytest.approx(
        100.0 * 2 * 120 * 10117120 / need)
    assert 12.0 < read(_traced()) < 22.0
    # 128 rows and the issue's ~75 k live tokens: ~17 %
    full = dict(_traced(), counters={
        'ssm_state_rows_updated_total': 200 * 128 * 26},
        decode_bytes_per_step=flops_jamba.decode_bytes_per_step(
            M, 75000, 128))
    assert read(full) == pytest.approx(17.4, abs=0.1)


def test_ssm_decode_state_roofline_on_made_up_facts():
    read = _reader('ssm_decode_state_roofline').read
    need = 2 * 200 * 120 * 26 * ROW
    facts = _traced(**{'mosaic:ssm_decode_update': 0.3,
                       'mosaic:ssm_decode_conv': 0.05,
                       'mosaic:ssm_prefill_scan': 0.2,
                       'mosaic:paged_decode_attention': 0.1, 'fusion': 0.9})
    # the bytes need need / 5 s / peak of every second; the two kernels
    # run in 0.35 / 2 of every second
    assert read(facts) == pytest.approx(
        100.0 * (need / 5.0 / 819e9) / (0.35 / 2.0))
    assert 0 < read(facts) < 100.0
    assert flops_jamba.ssm_decode_state_bytes(M, 1) == 2 * ROW


def test_ssm_prefill_scan_roofline_on_made_up_facts():
    read = _reader('ssm_prefill_scan_roofline').read
    # 35 dispatches of 26 scans; what the scan's operation moves: a row's
    # delta, delta * u, y and B, C (the gate's z is applied outside it)
    need = 4 * (9000 * 26 * (3 * 5120 + 32) + 35 * 26 * 2 * 16 * 5120)
    assert flops_jamba.ssm_prefill_scan_bytes(M, 9000 * 26, 35 * 26) == need
    facts = _traced(**{'mosaic:ssm_prefill_scan': 0.3,
                       'mosaic:ssm_decode_update': 0.4, 'fusion': 0.9})
    assert read(facts) == pytest.approx(
        100.0 * (need / 5.0 / 819e9) / (0.3 / 2.0))
    # a share of BYTES for a scan the VPU and the EUP bound: the reader
    # says what that does to the reading
    assert 0 < read(facts) < 10.0
    assert 'share of\nBYTES' in _reader('ssm_prefill_scan_roofline').__doc__
    # 6 operations a state entry a row
    assert flops_jamba.ssm_scan_flops(M, 1) == 6 * 16 * 5120


def test_the_accepted_readers_the_cell_lists_read_this_configuration():
    """`decode_hbm_share` divides this configuration's
    `decode_bytes_per_step` (weights + the two attention layers' live K/V
    + the state) by the step's time and stays under 100 at the chip's
    peak; `paged_decode_attention_roofline`, which the cell is NOT listed
    under, would read the two attention layers' rows at this
    configuration's 1 024 B a row."""
    facts = _traced()
    need = facts['decode_bytes_per_step']
    at_peak = dict(facts, histograms={
        'decode_step_seconds': (200, 200 * need / 819e9)})
    assert _reader('decode_hbm_share').read(at_peak) == pytest.approx(100.0)
    assert 0 < _reader('decode_hbm_share').read(facts) < 100.0
    assert _reader('decode_step_ms').read(facts) == pytest.approx(22.0)
    read = _reader('paged_decode_attention_roofline').read
    traced = _traced(**{'mosaic:paged_decode_attention': 0.05})
    assert read(traced) == pytest.approx(
        100.0 * (200 * 120 * 600 * 2 * 1024 / 5.0 / 819e9) / (0.05 / 2.0))
    # the readers of experts find none of their counters here
    for name in ('moe_experts_touched_share', 'moe_held_assignment_share',
                 'kv_window_read_share'):
        assert _reader(name).read(facts) is None


@pytest.mark.parametrize('name,op,counters', [
    ('ssm_decode_state_roofline', 'mosaic:ssm_decode_update',
     lambda s: {'ssm_state_rows_updated_total': int(819e9 * s / (2 * ROW))}),
    ('ssm_prefill_scan_roofline', 'mosaic:ssm_prefill_scan',
     lambda s: {'ssm_prefill_rows_total':
                int(819e9 * s / (4 * (3 * 5120 + 32)))})])
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

def test_jamba_control_main_at_toy_width(capsys):
    from benchmark.reference import jamba_control
    rc = jamba_control.main([
        TOY_CONFIG, os.path.join(HERE, 'traffic', 'toy-serve-jamba.json'),
        '5', '3000000009'])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith('{')]
    assert rc == 0 and len(lines) == 4
    always = {'stale-state', 'no-inner-norms', 'no-D', 'no-conv-bias',
              'bfloat16', 'bfloat16-state', 'rope-on-attention'}
    for out in lines:
        n = out['prompt_len']
        assert out['rows'] == min(25, 72 - n + 1)
        # the same row of the pools served every prompt: no reading shows
        # the one before
        assert out['logits_vs_ref'][1] < 1e-4
        assert out['refused_by_logits_rms'] is False
        assert out['greedy_margin_worst'] == 0.0
        last = n - (n - 1) // 16 * 16
        want = set(always)
        if n > 16:
            want.add('chunk-edge')
        if last not in (8, 16):
            want.add('pad-rows')
        assert set(out['controls']) == want
        for name, reading in out['controls'].items():
            floor = 1e-5 if name == 'bfloat16-state' else 2e-4
            assert reading['logits_vs_ref'][1] > floor, name
