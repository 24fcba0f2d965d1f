"""Server (serving/generate.py, the loop thread). The `admit` part of
decode_host_gap_ms: eviction of expired requests and the top-of-loop
admission (queue pop, block plan, block allocation; the prefill inside it
is a phase of its own). generate_loop_seconds_total{phase=admit} over the
window / the window's decode steps. Moves itl_p95_ms."""
from benchmark import phase_counters


def read(facts):
    return phase_counters.decode_gap_ms(facts, ('admit',))
