"""Trainer API (executor.py). The `compile` part of setup_program_s: XLA's
compile where the persistent cache missed (JAX's backend_compile_duration
less the retrieval inside it). Near 0 in a warm run.
program_setup_seconds_total{stage=compile} (paddle_tpu/coldstart.py), the
process' cumulative counters at the end of the run. Moves setup_s."""
from benchmark import setup_stages


def read(facts):
    return setup_stages.setup_program_s(('compile',))
