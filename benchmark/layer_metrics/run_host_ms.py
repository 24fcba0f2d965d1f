"""Trainer API (executor.py). The host's part of an Executor.run round the
step program: the self time of its prepare, dispatch and commit phases, as
executor_run_phase_seconds_total{phase=...} moved over the window, /
executor_run_total's movement. (`fetch`, the fourth phase, is the wait for
the device.) Moves train_tokens_per_s."""
from benchmark import phase_counters


def read(facts):
    return phase_counters.run_host_ms(facts)
