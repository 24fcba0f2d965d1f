"""What the per-layer metrics that read the program's phase counters
share. The program (paddle_tpu/monitor.py `phase`) adds each phase's self
time in seconds to a counter series `<counter>{phase=<name>}`;
facts['counters'] is monitor.counter_delta() over the measured window, a
flat dict of the series that moved. A program without the counter (one
from before the phases) has no such key: every function here then returns
None, and the reader's metric is left out of the line."""


def phase_seconds(counters, counter, phases=None):
    """Seconds that `counter{phase=p}` moved, summed over `phases` (every
    phase of the counter when None); None when none of them is there."""
    prefix = counter + '{phase='
    found = [v for k, v in counters.items()
             if k.startswith(prefix)
             and (phases is None or k[len(prefix):-1] in phases)]
    return float(sum(found)) if found else None


def per_ms(seconds, count):
    """Milliseconds each: None with nothing to divide, or by nothing."""
    if seconds is None or not count:
        return None
    return 1e3 * seconds / count


# the loop phases during which the device has no step to run: the host's
# part of the gap between two decode steps
DECODE_GAP_PHASES = ('admit', 'feed', 'dispatch', 'deliver')
# Executor.run's phases before and after the step program: the host's part
# of the gap between two train steps ('fetch' is the wait for the device)
RUN_HOST_PHASES = ('prepare', 'dispatch', 'commit')


def decode_gap_ms(facts, phases=DECODE_GAP_PHASES):
    return per_ms(phase_seconds(facts.get('counters', {}),
                                'generate_loop_seconds_total', phases),
                  facts.get('decode_steps'))


def run_host_ms(facts, phases=RUN_HOST_PHASES):
    counters = facts.get('counters', {})
    return per_ms(phase_seconds(counters, 'executor_run_phase_seconds_total',
                                phases),
                  counters.get('executor_run_total'))
