"""Model step (core/lowering.py). The `trace` part of setup_program_s: Program
-> jaxpr, lower_ops' walk over the IR with every op's lowering (JAX's
jaxpr_trace_duration as self time, and the frames round each entry's
making). program_setup_seconds_total{stage=trace} (paddle_tpu/coldstart.py),
the process' cumulative counters at the end of the run. Moves setup_s."""
from benchmark import setup_stages


def read(facts):
    return setup_stages.setup_program_s(('trace',))
