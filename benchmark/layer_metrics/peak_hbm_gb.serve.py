"""Device. memory_stats()['peak_bytes_in_use'] of the fullest chip after
the window, in GB (1e9 bytes). Moves serve_tokens_per_s."""


def read(facts):
    peak = facts.get('memory_peak_bytes')
    return peak / 1e9 if peak else None
