"""Trainer API (paddle_tpu/__init__.py). The `import` part of setup_program_s:
the package's own import (jax's too where the process had not imported it
yet). program_setup_seconds_total{stage=import} (paddle_tpu/coldstart.py),
the process' cumulative counters at the end of the run. Moves setup_s."""
from benchmark import setup_stages


def read(facts):
    return setup_stages.setup_program_s(('import',))
