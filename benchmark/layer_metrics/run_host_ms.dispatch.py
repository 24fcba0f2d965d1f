"""Trainer API (executor.py). The `dispatch` part of run_host_ms: the
compiled call, until it returns with the step staged.
executor_run_phase_seconds_total{phase=dispatch} over the window /
executor_run_total's movement. Moves train_tokens_per_s."""
from benchmark import phase_counters


def read(facts):
    return phase_counters.run_host_ms(facts, ('dispatch',))
