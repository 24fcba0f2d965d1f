"""Server (serving/generate.py `_deliver`). Of the token gaps delivered in
the window, the share inside which another request was admitted:
generate_token_gaps_total{held=admission} / both labels, in percent.
Where it is near 5 % the 95th percentile of the gaps sits on the edge
between the two kinds and itl_p95_ms is noisy; well above, itl_p95_ms is
token_gap_ms.admission's neighbourhood. A program without the counter, or
a window without a gap, reads nothing. Moves itl_p95_ms."""


def read(facts):
    counters = facts.get('counters', {})
    held = counters.get('generate_token_gaps_total{held=admission}', 0)
    total = held + counters.get('generate_token_gaps_total{held=none}', 0)
    return 100.0 * held / total if total else None
