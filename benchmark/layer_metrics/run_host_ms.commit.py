"""Trainer API (executor.py). The `commit` part of run_host_ms:
scope.update, the goodput hook, LoD propagation.
executor_run_phase_seconds_total{phase=commit} over the window /
executor_run_total's movement. Moves train_tokens_per_s."""
from benchmark import phase_counters


def read(facts):
    return phase_counters.run_host_ms(facts, ('commit',))
