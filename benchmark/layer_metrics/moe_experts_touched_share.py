"""Model step (ops/moe_ops.py moe_ffn, counted by serving/generate.py).
Of the experts a layer holds, the share that a dispatch (a decode step's
live rows, or one prefill's real rows) routed at least one row to:
moe_experts_touched_total / (moe_layer_steps_total x num_experts), both as
they moved over the window, in percent. It is what a decode step has to
read of the expert weights: 16 rows x 8 of 64 touch 64 x (1 - 0.875^16) =
56 under even routing, 87.6 %; a prefill touches all. A program without
the counters (no experts, or from before them) reads nothing. Moves
serve_tokens_per_s."""


def read(facts):
    c = facts.get('counters', {})
    layer_steps = c.get('moe_layer_steps_total')
    experts = facts.get('config', {}).get('num_experts')
    if not layer_steps or not experts:
        return None
    return 100.0 * c.get('moe_experts_touched_total', 0) \
        / (layer_steps * experts)
