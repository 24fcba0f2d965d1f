"""Trainer API (executor.py, parallel/spmd.py). The `place` part of
setup_program_s: state put onto the device, onto the mesh, or re-laid for a
bound entry - the host's time in the calls that move an array.
program_setup_seconds_total{stage=place} (paddle_tpu/coldstart.py), the
process' cumulative counters at the end of the run. Moves setup_s."""
from benchmark import setup_stages


def read(facts):
    return setup_stages.setup_program_s(('place',))
