"""Server (serving/generate.py). The mean time a request waited between
submit() and its admission by the loop thread:
generate_queue_wait_seconds_total / generate_admit_total, both as they
moved over the window. Moves ttft_p95_ms."""
from benchmark import phase_counters


def read(facts):
    counters = facts.get('counters', {})
    return phase_counters.per_ms(
        counters.get('generate_queue_wait_seconds_total'),
        counters.get('generate_admit_total'))
