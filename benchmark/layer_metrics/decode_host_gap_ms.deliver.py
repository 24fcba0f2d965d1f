"""Server (serving/generate.py, the loop thread). The `deliver` part of
decode_host_gap_ms: per-slot bookkeeping, tokens out to their streams,
finished slots released. generate_loop_seconds_total{phase=deliver} over
the window / the window's decode steps. Moves itl_p95_ms."""
from benchmark import phase_counters


def read(facts):
    return phase_counters.decode_gap_ms(facts, ('deliver',))
