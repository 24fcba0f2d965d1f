"""Trainer API (executor.py). The `cache_load` part of setup_program_s: cached
executables read and deserialised (JAX's cache_retrieval_time_sec). Near 0
in a cold run. program_setup_seconds_total{stage=cache_load}
(paddle_tpu/coldstart.py), the process' cumulative counters at the end of
the run. Moves setup_s."""
from benchmark import setup_stages


def read(facts):
    return setup_stages.setup_program_s(('cache_load',))
