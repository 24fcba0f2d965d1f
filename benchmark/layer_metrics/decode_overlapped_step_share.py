"""Server (serving/generate.py, the loop thread). Of the window's decode
steps, the share dispatched while the step before them was still
unfetched — the loop's pipeline was full, and the step's input tokens
never left the device: generate_overlapped_steps_total / the count of
decode_step_seconds, both as they moved over the window, in percent. A
closed loop that keeps its slots resident should read near 100; every
idle spell and every failed step starts the pipeline anew with one step
that has no predecessor. The loop books a step's observation, then its
counter, and the serve driver reads the histograms outside the counters
at both ends of the window, so the counter's steps are among the
histogram's but for ONE whose two bookings the opening snapshots split:
that one is cut here. A count that passes the steps by more is no skew
of snapshots but a fault in the counting, and is returned as it reads.
A counter that did not move is not among facts['counters']; that the
program counts at all is read from the engine's stats
('overlapped_steps'). A program from before the pipeline reads nothing.
Moves serve_tokens_per_s."""


def read(facts):
    steps, _total = facts.get('histograms', {}).get('decode_step_seconds',
                                                    (0, 0))
    if not steps or 'overlapped_steps' not in facts.get('engine_stats', {}):
        return None
    overlapped = facts.get('counters', {}).get(
        'generate_overlapped_steps_total', 0)
    if overlapped == steps + 1:
        overlapped = steps
    return 100.0 * overlapped / steps
