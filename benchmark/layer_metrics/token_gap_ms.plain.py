"""Server (serving/generate.py `_deliver`). The mean delivered token gap
inside which no admission completed:
the loop's period a row, beside decode_step_ms.
generate_token_gap_seconds_total{held=none} /
generate_token_gaps_total{held=none}, both as they moved over the
window. A program without the counters, or a window without such a gap,
reads nothing. Moves itl_p95_ms."""
from benchmark import phase_counters


def read(facts):
    counters = facts.get('counters', {})
    return phase_counters.per_ms(
        counters.get('generate_token_gap_seconds_total{held=none}'),
        counters.get('generate_token_gaps_total{held=none}'))
