"""Server (serving/generate.py, the loop thread). The share of the loop's
wall time that no phase covers: 100 x (1 - the sum of every
generate_loop_seconds_total{phase=...} / generate_loop_wall_seconds_total),
as they moved over the window. What the phases' self times leave out is
unmeasured host work. Moves itl_p95_ms."""
from benchmark import phase_counters


def read(facts):
    counters = facts.get('counters', {})
    covered = phase_counters.phase_seconds(counters,
                                           'generate_loop_seconds_total')
    wall = counters.get('generate_loop_wall_seconds_total')
    if covered is None or not wall:
        return None
    return 100.0 * (1.0 - covered / wall)
