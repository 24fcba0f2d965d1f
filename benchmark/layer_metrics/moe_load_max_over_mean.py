"""Model step (ops/moe_ops.py moe_ffn, counted by serving/generate.py).
The straggler expert: the rows the busiest expert of each layer got,
summed over the window's dispatches and layers (moe_max_expert_rows_total),
over the rows an expert would get if routing were even
(moe_assignments_total / num_experts). 1 is perfectly even; the grouped
matmul's longest group is this many times the mean one. A program without
the counters reads nothing. Moves itl_p95_ms."""


def read(facts):
    c = facts.get('counters', {})
    assigned = c.get('moe_assignments_total')
    experts = facts.get('config', {}).get('num_experts')
    if not assigned or not experts:
        return None
    return c.get('moe_max_expert_rows_total', 0) / (assigned / experts)
