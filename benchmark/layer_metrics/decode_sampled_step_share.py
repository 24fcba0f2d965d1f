"""Model step (ops/kv_cache_ops.py sample_next_token, counted by
serving/generate.py). Of the window's decode steps, the share dispatched
with at least one resident row at temperature > 0:
generate_sampled_steps_total / the count of decode_step_seconds, both as
they moved over the window, in percent. The op branches on the device on
that condition: such a step sorts the vocabulary for every row, any other
takes the argmax alone, so 0 says every step of the window was the
argmax. A counter that did not move is not among facts['counters']; that
the program counts at all is read from the engine's stats
('sampled_steps'). A program from before the counter reads nothing.
Moves itl_p95_ms."""


def read(facts):
    steps, _total = facts.get('histograms', {}).get('decode_step_seconds',
                                                    (0, 0))
    if not steps or 'sampled_steps' not in facts.get('engine_stats', {}):
        return None
    return 100.0 * facts.get('counters', {}).get(
        'generate_sampled_steps_total', 0) / steps
