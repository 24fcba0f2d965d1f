"""Model step (core/lowering.py). The `lower` part of setup_program_s: jaxpr ->
StableHLO (JAX's jaxpr_to_mlir_module_duration).
program_setup_seconds_total{stage=lower} (paddle_tpu/coldstart.py), the
process' cumulative counters at the end of the run. Moves setup_s."""
from benchmark import setup_stages


def read(facts):
    return setup_stages.setup_program_s(('lower',))
