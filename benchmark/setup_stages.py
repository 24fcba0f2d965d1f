"""What the per-layer metrics of set-up share. The program
(paddle_tpu/coldstart.py) books every stage of a process' start — import,
build, trace, lower, compile, cache_load, place, first_run — as self time
in seconds under `program_setup_seconds_total{program=<name>,stage=<s>}`
(`import` has no program). Set-up is over before a window opens, so
facts['counters'], the movement over the window, holds none of it: the
readers take the process' CUMULATIVE counters from the program themselves
(`correct` holds the window to no compile, so cumulative is set-up).
JAX's work outside the package — the benchmark's own weights and
reference — is not in the series (`paddle_tpu.coldstart.outside()` has
it). A program without the series (one from before the stages) has no
such key: every function here then returns None, and the reader's metric
is left out of the line."""

SERIES = 'program_setup_seconds_total'
STAGES = ('import', 'build', 'trace', 'lower', 'compile', 'cache_load',
          'place', 'first_run')


def labels_of(key):
    """{'program': .., 'stage': ..} of one key of the series."""
    return dict(kv.split('=', 1)
                for kv in key[len(SERIES) + 1:-1].split(','))


def stage_seconds(counters, stages=STAGES):
    """Seconds booked under `stages`; None when the series is not there
    at all."""
    mine = {k: v for k, v in counters.items() if k.startswith(SERIES + '{')}
    if not mine:
        return None
    total = 0.0
    for key, seconds in mine.items():
        if labels_of(key).get('stage') in stages:
            total += seconds
    return float(total)


def setup_program_s(stages=STAGES):
    from paddle_tpu import monitor
    return stage_seconds(monitor.counters(), stages)
