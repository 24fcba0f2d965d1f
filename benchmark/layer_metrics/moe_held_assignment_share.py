"""Model step (ops/moe_ops.py moe_ffn with `experts_held`, counted by
serving/generate.py). Of the (row, expert) assignments the router made,
the share that went to an expert THIS chip holds and so was computed
here: moe_held_assignments_total / moe_assignments_total, both as they
moved over the window, in percent. 64 of 256 experts held read 25 % under
even routing; a router that favours the held experts reads more, and the
grouped matmuls here do more than the chip's share of the layer's work. A
layer that holds every expert reads 100. A program without the counter
(no experts, or from before it) reads nothing. Moves
serve_tokens_per_s."""


def read(facts):
    c = facts.get('counters', {})
    made = c.get('moe_assignments_total')
    if not made or 'moe_held_assignments_total' not in c:
        return None
    return 100.0 * c['moe_held_assignments_total'] / made
