"""The OLMoE configuration's benchmark files (ISSUE 28): a toy OLMoE cell
and a toy open-loop cell through run.py end to end on the CPU (their own
toy manifest; test_bench_run.py's fixture overrides the platform check),
flops_moe's formulae
against a count of param_shapes, the readers of the three new per-layer
metrics on made-up facts, the comparison script's main() at toy width, and
the sizing of the cell against the device-less v5e."""
import importlib.util
import json
import os

import numpy as np
import pytest

from benchmark import flops_moe
from benchmark.models import olmoe

from test_bench_run import _last_json, run_on_cpu          # noqa: F401

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TOY_MANIFEST = os.path.join(HERE, 'fixtures', 'BENCHMARK.toy.olmoe.json')
CONFIG = os.path.join(ROOT, 'benchmark', 'configs',
                      'olmoe-1b-7b-0125-l6.json')


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


E2E = {'serve_tokens_per_s', 'itl_p95_ms', 'setup_s'}
# on the CPU peak_hbm_gb.serve has nothing to read, and no operation of the
# trace is named mosaic:ragged-dot*: both readers return nothing
TRACED = {
    'toy-serve-moe': {'decode_step_ms', 'decode_hbm_share',
                      'device_idle_share.serve', 'ttft_p95_unbounded_ms',
                      'ttft_mean_unbounded_ms',
                      'decode_host_gap_ms.deliver',
                      'moe_experts_touched_share',
                      'moe_load_max_over_mean'},
    'toy-serve-open': {'decode_step_ms', 'decode_hbm_share',
                       'device_idle_share.serve', 'ttft_p95_unbounded_ms',
                       'ttft_mean_unbounded_ms',
                       'decode_host_gap_ms.deliver'},
}


@pytest.mark.parametrize('workload', sorted(TRACED))
def test_end_to_end_line(run_on_cpu, capsys, workload):
    rc = run_on_cpu.main(['--workload', workload, '--seed', '3000000001',
                          '--seconds', '0.5', '--trace', '0'],
                         manifest_path=TOY_MANIFEST)
    out, lines = _last_json(capsys)
    assert rc == 0 and out['correct'] is True and out['failed'] == 0
    assert out['attempted'] > 0 and set(out['metrics']) == E2E
    assert all(v['value'] > 0 for v in out['metrics'].values())
    check = [ln for ln in lines if 'check: prompt of' in ln]
    assert len(check) == 2 and all('generate_once: True' in ln
                                   for ln in check)


@pytest.mark.parametrize('workload', sorted(TRACED))
def test_traced_line(run_on_cpu, capsys, workload):
    rc = run_on_cpu.main(['--workload', workload, '--seed', '7',
                          '--seconds', '0.7', '--trace', '1'],
                         manifest_path=TOY_MANIFEST)
    out, _lines = _last_json(capsys)
    assert rc == 0 and out['correct'] is True
    assert set(out['metrics']) == TRACED[workload]
    if workload == 'toy-serve-moe':
        touched = out['metrics']['moe_experts_touched_share']['value']
        skew = out['metrics']['moe_load_max_over_mean']['value']
        # 3 rows x 2 of 8 experts a step touch at most 6, at least 2; the
        # busiest expert has at least the mean and at most every row
        assert 25.0 <= touched <= 100.0 and 1.0 <= skew <= 8.0
        assert 0 < out['metrics']['decode_hbm_share']['value'] < 100


# ---- flops_moe against a count of the parameters ---------------------------

def _count(shapes, pick=lambda name: True):
    return sum(int(np.prod(s)) for n, s in shapes.items() if pick(n))


@pytest.mark.parametrize('path', [
    CONFIG, os.path.join(HERE, 'configs', 'toy-olmoe.json')],
    ids=['olmoe-1b-7b-0125-l6', 'toy-olmoe'])
def test_flops_moe_counts_what_param_shapes_lists(path):
    with open(path) as f:
        m = json.load(f)
    shapes = olmoe.param_shapes(m)
    n = m['num_hidden_layers']
    assert flops_moe.param_count(m) == _count(shapes)
    assert flops_moe.layer_param_count(m) == _count(
        shapes, lambda k: k.startswith('layer_0.'))
    assert flops_moe.expert_param_count(m) * m['num_experts'] * n == _count(
        shapes, lambda k: '.moe.' in k and 'router' not in k)
    kv_width = m['num_attention_heads'] * (m['hidden_size']
                                           // m['num_attention_heads'])
    assert flops_moe.kv_bytes_per_token(m) == 2 * n * kv_width * 4
    # a decode step with every slot idle but one reads k experts a layer;
    # with very many rows it reads every weight but the embedding
    one = flops_moe.decode_bytes_per_step(m, 0, 1)
    dense = _count(shapes, lambda k: ('.moe.' not in k or 'router' in k)
                   and k != 'tok_emb.w')
    per_expert = flops_moe.expert_param_count(m)
    assert one == pytest.approx(
        4 * (dense + n * m['num_experts_per_tok'] * per_expert
             + m['hidden_size']))
    many = flops_moe.decode_bytes_per_step(m, 0, 10000)
    assert many == pytest.approx(
        4 * (_count(shapes) - _count(shapes, lambda k: k == 'tok_emb.w')
             + 10000 * m['hidden_size']), rel=1e-6)
    assert flops_moe.decode_bytes_per_step(m, 100, 1) - one == \
        100 * flops_moe.kv_bytes_per_token(m)


def test_the_published_configuration_is_what_the_issue_sized():
    with open(CONFIG) as f:
        m = json.load(f)
    # a layer 419.6 M parameters, 402.7 M of them in the experts; 6 layers
    # + embedding and head = 10.9 GB in float32; 56 of 64 experts a step
    assert flops_moe.layer_param_count(m) == pytest.approx(419.6e6, rel=1e-3)
    assert m['num_experts'] * flops_moe.expert_param_count(m) == \
        pytest.approx(402.7e6, rel=1e-3)
    assert 4 * flops_moe.param_count(m) == pytest.approx(10.89e9, rel=2e-3)
    assert flops_moe.expected_experts_touched(m, 16) == pytest.approx(
        56.4, abs=0.1)
    assert flops_moe.decode_bytes_per_step(m, 16 * 400, 16) == \
        pytest.approx(9.9e9, rel=0.03)
    cfg = olmoe.lm_config(m, 1280, False)
    assert (cfg.norm, cfg.position, cfg.ffn) == ('rms_norm', 'rope', 'moe')
    assert (cfg.n_head, cfg.head_dim, cfg.kv_width) == (16, 128, 2048)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.expert_width) == \
        (64, 8, 1024) and not cfg.bias and cfg.qk_norm
    with pytest.raises(ValueError):
        olmoe.lm_config(m, 1280, True)             # training: later
    with pytest.raises(ValueError):
        olmoe.lm_config(dict(m, hidden_act='gelu'), 1280, False)


# ---- the readers -------------------------------------------------------------

def _reader(name):
    return _load(os.path.join(ROOT, 'benchmark', 'layer_metrics',
                              name + '.py'), 'reader_' + name.replace(
                                  '.', '_'))


M = {'num_experts': 64, 'hidden_size': 2048, 'intermediate_size': 1024}
# a window of 100 decode steps of 6 layers, 16 rows x 8 a layer-step, 56
# experts touched a layer-step, the busiest expert with 6 rows
COUNTERS = {'moe_layer_steps_total': 600, 'moe_assignments_total': 76800,
            'moe_experts_touched_total': 33600,
            'moe_max_expert_rows_total': 3600}


def test_the_counter_readers_on_made_up_facts():
    facts = {'counters': COUNTERS, 'config': M}
    assert _reader('moe_experts_touched_share').read(facts) == \
        pytest.approx(87.5)
    assert _reader('moe_load_max_over_mean').read(facts) == \
        pytest.approx(3.0)
    for name in ('moe_experts_touched_share', 'moe_load_max_over_mean',
                 'moe_ffn_hbm_share'):
        # a program without the counters (the parent commit, a model
        # without experts): nothing to read, nothing raised
        assert _reader(name).read({'counters': {}, 'config': M}) is None
        assert _reader(name).read(
            {'counters': {}, 'config': {'d_model': 8}, 'trace': None}) \
            is None


def test_moe_ffn_hbm_share_on_made_up_facts():
    read = _reader('moe_ffn_hbm_share').read
    need = flops_moe.grouped_matmul_bytes(M, 33600, 76800)
    # 33 600 touched experts x 25.2 MB is what counts; activations ~2 %
    assert need == pytest.approx(33600 * 3 * 2048 * 1024 * 4, rel=0.03)
    peaks = {'hbm_bytes_per_s': 819e9}
    facts = {'counters': COUNTERS, 'config': M, 'peaks': peaks,
             'window_s': 4.0,
             'trace': {'window_s': 2.0, 'busy_s': 1.8, 'op_seconds': {
                 'mosaic:ragged-dot-none': 1.0,
                 'mosaic:ragged-dot-metadata': 0.25, 'fusion': 0.5}}}
    # the bytes need need / 4 s / peak of every second; the grouped
    # matmuls run in 1.25 / 2 of every second
    assert read(facts) == pytest.approx(
        100.0 * (need / 4.0 / 819e9) / (1.25 / 2.0))
    assert read(facts) < 105.0
    # no such operation in the trace (a CPU run, another lowering)
    quiet = dict(facts, trace={'window_s': 2.0, 'busy_s': 1.8,
                               'op_seconds': {'fusion': 0.5}})
    assert read(quiet) is None


# ---- the comparison script, as the chip runs it -----------------------------

def test_olmoe_control_main_at_toy_width(capsys):
    from benchmark.reference import olmoe_control
    rc = olmoe_control.main([
        os.path.join(HERE, 'configs', 'toy-olmoe.json'),
        os.path.join(HERE, 'traffic', 'toy-serve-moe.json'), '5',
        '3000000009'])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith('{')]
    assert rc == 0 and len(lines) == 4
    for out in lines:
        assert out['rows'] == 24 - out['prompt_len'] + 1   # max_len 24
        assert out['routing_rows_not_ref_top_k'] == 0.0
        assert out['logits_vs_ref_given_routing'][1] < 1e-4
        assert out['logits_vs_ref_own_routing'][1] < 1e-4
        assert set(out['controls']) == {'bfloat16', 'top-1', 'renormalised',
                                        'softmax-over-chosen'}


def test_size_serve_compiles_the_cell_for_the_v5e_without_a_chip():
    """benchmark/size_serve.py in a process of its own (it sets the
    device-less TPU topology's environment), at ONE of the six layers to
    stay inside the suite's time: the decode step and the 768 bucket
    through XLA:TPU and Mosaic."""
    import subprocess
    import sys
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'benchmark', 'size_serve.py'),
         '--config', CONFIG, '--traffic',
         os.path.join(ROOT, 'benchmark', 'traffic', 'chat16-closed.json'),
         '--layers', '1'],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS='cpu'))
    assert done.returncode == 0, done.stderr[-2000:]
    out = json.loads(done.stdout.strip().splitlines()[-1])
    assert out['layers'] == 1
    step, fill = out['decode_step'], out['prefill_b768']
    assert 'refused' not in step and 'refused' not in fill
    # one layer 1.68 GB + embedding and head 0.82 + 1280 blocks of one
    # layer 0.34 GB
    assert step['argument_gb'] == pytest.approx(2.84, abs=0.05)
    # dropless and sparse: 16 x 8 and 768 x 8 expert rows, not x 64
    expert_row = 3 * 2 * 2048 * 1024
    other = 2 * (2048 * 3 * 2048 + 2048 * 2048 + 2048 * 64)
    head = 2 * 2048 * 50304
    assert step['flops'] == pytest.approx(
        16 * (8 * expert_row + other + head), rel=0.1)
    assert fill['flops'] < 768 * (8 * expert_row + other) * 1.5 + head * 2
    # the paged attention kernel and three grouped matmuls (+ metadata)
    assert step['mosaic_calls'] >= 4 and step['ragged_dots'] >= 3
