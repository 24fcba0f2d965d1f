"""Server (serving/generate.py `_prefill_call`). What an admission waits
for the decode steps dispatched before its prefill: the
`block_until_ready()` over the steps in flight.
generate_loop_seconds_total{phase=prefill.drain} over the window /
generate_admit_total. The phase opens only where a step is in flight: a
window whose admissions found none reads 0, a program without the phases
(no `prefill.fetch` either: the parent commit) nothing. Moves
itl_p95_ms."""
from benchmark import phase_counters

LOOP = 'generate_loop_seconds_total'


def read(facts):
    counters = facts.get('counters', {})
    if phase_counters.phase_seconds(counters, LOOP,
                                    ('prefill.fetch',)) is None:
        return None
    return phase_counters.per_ms(
        phase_counters.phase_seconds(counters, LOOP,
                                     ('prefill.drain',)) or 0.0,
        counters.get('generate_admit_total'))
