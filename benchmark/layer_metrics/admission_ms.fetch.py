"""Server (serving/generate.py `_prefill_call`). The prefill alone: from the
decode steps in flight seen complete to the prefill's token on the host,
i.e. the prefill program's device time and then the device-to-host copy
of its token, during which the device is idle.
generate_loop_seconds_total{phase=prefill.fetch} over the window /
generate_admit_total. Moves itl_p95_ms."""
from benchmark import phase_counters


def read(facts):
    counters = facts.get('counters', {})
    return phase_counters.per_ms(
        phase_counters.phase_seconds(counters, 'generate_loop_seconds_total',
                                     ('prefill.fetch',)),
        counters.get('generate_admit_total'))
