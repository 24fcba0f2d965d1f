"""chip_smoke.py's phases at toy width on the virtual CPU mesh, with the
fused units through the Pallas interpreter — the same functions, asserts
and dispatch-table checks the chip run makes at full width — plus the
script's refusal to run without a TPU."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def _toy():
    cfg = chip_smoke.SmokeConfig()
    # widths that still tile for every kernel: d and V fill 128 lanes,
    # batch * seq gives the row kernels whole 128-row blocks
    cfg.lm = dict(vocab_size=128, seq_len=16, d_model=128, n_head=2,
                  n_layer=1, d_ff=256, dropout=0.1, attn_dropout=0.0,
                  use_flash_attention=True)
    cfg.batch = 32                # 512 rows: 128 per shard under data=4
    cfg.slots = 8                 # the decode step's rows: one 8-row block
    cfg.max_len = 32
    cfg.block_size = 8            # whole (8, 128) tiles: the paged kernel's rule
    cfg.prompt_buckets = [8, 16]
    cfg.max_new_tokens = 4
    cfg.prompt_lens = [3, 8, 5, 12, 16, 7]
    cfg.shared_prefix = 8
    cfg.shared_tails = [2, 6]
    cfg.platform = 'cpu'
    def interp(tiers):            # the lookup is `off` at every tier
        return {op: 'off' if op == 'lookup_table' else 'interpret'
                for op in tiers}
    # AMP stands the FFN kernel down in training; the f32 serving
    # programs run it (these panels fit its VMEM predicate)
    cfg.train_tiers = dict(interp(cfg.train_tiers), fused_ffn_tail='xla')
    cfg.serve_tiers = dict(interp(cfg.serve_tiers),
                           kv_prefix_attention='xla')
    cfg.mosaic_kernels = {}       # no Mosaic in interpret mode
    return cfg


def test_phases_at_toy_width(monkeypatch):
    monkeypatch.setenv('PADDLE_FUSED_TIER', 'interpret')
    cfg = _toy()
    a, lm, scope = chip_smoke.phase_train(cfg)
    assert a['steps'] == 7 and len(a['losses']) == 3
    b = chip_smoke.phase_serve(cfg, lm, scope)
    assert b['requests'] == 8 and b['tokens_generated'] == 8 * 4
    assert b['prefix_hits'] >= 1
    c = chip_smoke.phase_dp(cfg, a['losses'])
    assert c['devices'] == 4 and c['max_abs_diff_vs_A'] <= cfg.dp_loss_tol


def test_verdict_line_has_exactly_the_contract_keys():
    import jax
    devs = jax.devices()
    line = chip_smoke.verdict_line(True, devs)
    assert '\n' not in line
    out = json.loads(line)
    assert sorted(out) == ['device', 'ok'] and out['ok'] is True
    assert out['device'] == {'platform': devs[0].platform,
                             'kind': devs[0].device_kind,
                             'count': len(devs)}


def test_script_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    p = subprocess.run([sys.executable, os.path.join(ROOT, 'chip_smoke.py')],
                       env=env, cwd=ROOT, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert 'no TPU' in p.stderr
    assert '"ok"' not in p.stdout
