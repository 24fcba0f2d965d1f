"""Model step (framework.program_guard). The `build` part of setup_program_s:
the Python front end inside program_guard - layer calls, append_backward,
minimize, AMP's rewrite. program_setup_seconds_total{stage=build}
(paddle_tpu/coldstart.py), the process' cumulative counters at the end of
the run. Moves setup_s."""
from benchmark import setup_stages


def read(facts):
    return setup_stages.setup_program_s(('build',))
