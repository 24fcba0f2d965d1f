"""Server (serving/generate.py, the loop thread). The host's part of the gap
between two decode steps: the self time of the loop phases during which the
device has no step to run — admit, feed, dispatch, deliver, as
generate_loop_seconds_total{phase=...} moved over the window — / the
window's decode steps. Moves itl_p95_ms."""
from benchmark import phase_counters


def read(facts):
    return phase_counters.decode_gap_ms(facts)
