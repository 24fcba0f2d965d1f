"""Server (serving/generate.py). monitor histogram decode_step_seconds
(host clock round a step that ends in a fetch): sum and count read before
and after the window, the mean of the difference. Moves itl_p95_ms."""


def read(facts):
    n, total = facts.get('histograms', {}).get('decode_step_seconds', (0, 0))
    return 1e3 * total / n if n else None
