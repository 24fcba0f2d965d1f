"""Device. Device trace: 1 - union of device op intervals / traced window,
in percent; the same reduction as the result line's device.busy_s and
device.window_s. Moves serve_tokens_per_s."""


def read(facts):
    t = facts.get('trace')
    if not t:
        return None
    return 100.0 * (1.0 - t['busy_s'] / t['window_s'])
