"""Trainer API (executor.py). Device trace: the device's idle time per
Executor.run — (traced window - union of device op intervals) / steps in
the traced window. Moves train_tokens_per_s."""


def read(facts):
    t = facts.get('trace')
    if not t or not facts.get('traced_steps'):
        return None
    return 1e3 * (t['window_s'] - t['busy_s']) / facts['traced_steps']
