"""Server (serving/generate.py, the loop thread). The `feed` part of
decode_host_gap_ms: block growth, the numpy feed and the sampling
parameters of a decode step. generate_loop_seconds_total{phase=feed} over
the window / the window's decode steps. Moves itl_p95_ms."""
from benchmark import phase_counters


def read(facts):
    return phase_counters.decode_gap_ms(facts, ('feed',))
