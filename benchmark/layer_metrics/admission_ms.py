"""Server (serving/generate.py, the loop thread). What one admission costs
every resident stream: the `prefill` phase with the three nested in it
(`prefill.dispatch`, `prefill.drain`, `prefill.fetch`), summed over the
window / generate_admit_total. The stretch the prefill_seconds histogram
times, from the loop's phase counters; its three named parts are
admission_ms.dispatch / .drain / .fetch; the rest is `prefill`'s self
time (building the feed, bucketing, a chunked prompt's loop).

A program without the nested phases (the parent commit) reads nothing,
though its `prefill` phase moves. Moves itl_p95_ms."""
from benchmark import phase_counters

LOOP = 'generate_loop_seconds_total'
PHASES = ('prefill', 'prefill.dispatch', 'prefill.drain', 'prefill.fetch')


def read(facts):
    counters = facts.get('counters', {})
    if phase_counters.phase_seconds(counters, LOOP,
                                    ('prefill.fetch',)) is None:
        return None
    return phase_counters.per_ms(
        phase_counters.phase_seconds(counters, LOOP, PHASES),
        counters.get('generate_admit_total'))
