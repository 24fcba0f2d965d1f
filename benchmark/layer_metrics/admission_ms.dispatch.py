"""Server (serving/generate.py `_prefill_call`). The host's part of an
admission: the prefill's bound call, argument handling and enqueue
(a chunked prefill books every chunk), while the device finishes the
decode steps in flight and, in a host-paced loop, goes idle.
generate_loop_seconds_total{phase=prefill.dispatch} over the window /
generate_admit_total. Moves itl_p95_ms."""
from benchmark import phase_counters


def read(facts):
    counters = facts.get('counters', {})
    return phase_counters.per_ms(
        phase_counters.phase_seconds(counters, 'generate_loop_seconds_total',
                                     ('prefill.dispatch',)),
        counters.get('generate_admit_total'))
