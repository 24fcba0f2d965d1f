"""SPMD runners (parallel/spmd.py). The `commit` part of mesh_run_host_ms: the
scope rebind, LoDs, as executor_run_phase_seconds_total{phase=...} moved
over the window / executor_run_total's movement (counted where
CompiledProgram delegates to the runner). (`fetch`, the fourth phase, is the
wait for the device.) Moves train_tokens_per_s."""
from benchmark import phase_counters


def read(facts):
    return phase_counters.run_host_ms(facts, ('commit',))
