"""SPMD runners (parallel/spmd.py). The host's part of a sharded step round the
step program: the self time of DataParallelRunner.run's prepare, dispatch
and commit phases, as executor_run_phase_seconds_total{phase=...} moved over
the window / executor_run_total's movement (counted where CompiledProgram
delegates to the runner). (`fetch`, the fourth phase, is the wait for the
device.) Moves train_tokens_per_s."""
from benchmark import phase_counters


def read(facts):
    return phase_counters.run_host_ms(facts)
