"""Model step (core/lowering.py, models/transformer.py). Model FLOPs per
token (benchmark/flops.py: causal half, no recompute) x tokens/s of the
measured window / (chips x peak bf16 FLOP/s of peaks.json), in percent.
The bound is flops. Moves train_tokens_per_s."""


def read(facts):
    rate = facts['end_to_end'].get('train_tokens_per_s')
    if rate is None:
        return None
    peak = facts['chips'] * facts['peaks']['bf16_flops_per_s']
    return 100.0 * facts['flops_per_token'] * rate / peak
