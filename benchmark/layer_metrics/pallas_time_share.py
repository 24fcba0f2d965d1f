"""Kernels (ops/*_ops.py via kernel_tier.py). Device trace: time in the
events of Mosaic kernels (the repo's Pallas kernels: an 'XLA Ops' event
whose instruction is a custom call to "tpu_custom_call"; it carries the
`name=` of its pallas_call, e.g. jvp_flash_attention_fwd_) / device busy
time, in percent. Moves train_tokens_per_s."""
from benchmark.reduce_trace import MOSAIC


def read(facts):
    t = facts.get('trace')
    if not t or not t['busy_s']:
        return None
    inside = sum(s for name, s in t['op_seconds'].items()
                 if name.startswith(MOSAIC))
    return 100.0 * inside / t['busy_s']
