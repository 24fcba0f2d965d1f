"""Server (serving/generate.py `_deliver`). The mean delivered token gap
inside which another request was
admitted (the engine's count of completed admissions moved between the
row's two tokens): the gap that is itl_p95_ms wherever more than 5 % of
the gaps hold one.
generate_token_gap_seconds_total{held=admission} /
generate_token_gaps_total{held=admission}, both as they moved over the
window. A program without the counters, or a window without such a gap,
reads nothing. Moves itl_p95_ms."""
from benchmark import phase_counters


def read(facts):
    counters = facts.get('counters', {})
    return phase_counters.per_ms(
        counters.get('generate_token_gap_seconds_total{held=admission}'),
        counters.get('generate_token_gaps_total{held=admission}'))
