"""Server (serving/generate.py, the loop thread). The `dispatch` part of
decode_host_gap_ms: the bound decode-step call, until it returns with the
step staged. generate_loop_seconds_total{phase=dispatch} over the window /
the window's decode steps. Moves itl_p95_ms."""
from benchmark import phase_counters


def read(facts):
    return phase_counters.decode_gap_ms(facts, ('dispatch',))
