"""Server (serving/generate.py). monitor histogram prefill_seconds, the
mean of its movement over the window, in a cell whose tail of gaps between
tokens IS a neighbour's prefill landing between two decode steps. Moves
itl_p95_ms."""


def read(facts):
    n, total = facts.get('histograms', {}).get('prefill_seconds', (0, 0))
    return 1e3 * total / n if n else None
