"""Trainer API (executor.py). The `first_run` part of setup_program_s: the rest
of each frame round a new entry's first call - the dispatch of its first
execution, which is not waited for - and of the engine's warm-up round its
binds.
program_setup_seconds_total{stage=first_run} (paddle_tpu/coldstart.py), the
process' cumulative counters at the end of the run. Moves setup_s."""
from benchmark import setup_stages


def read(facts):
    return setup_stages.setup_program_s(('first_run',))
