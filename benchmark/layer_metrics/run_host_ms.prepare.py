"""Trainer API (executor.py). The `prepare` part of run_host_ms: feed
preparation, fingerprint and feed signature, cache lookup, state gather
from the scope, the run key.
executor_run_phase_seconds_total{phase=prepare} over the window /
executor_run_total's movement. Moves train_tokens_per_s."""
from benchmark import phase_counters


def read(facts):
    return phase_counters.run_host_ms(facts, ('prepare',))
