"""Server (serving/generate.py `_paged_plan`, serving/kv_blocks.py
`PrefixCache`). Of the prompt tokens the window's admissions brought, the
share that was NOT prefilled because it lay in shared blocks already:
kv_prefix_tokens_saved_total / prefill_prompt_tokens_total over the
measured window, in percent. A model with convolution layers resumes a
hit's suffix from the shared block's tail as well as behind its keys and
values (ops/short_conv_ops.py): a change that turns hits into misses
drops this from what the traffic shares to 0.

A program without the second counter (the parent commit), or a window
without an admission, reads nothing. Moves serve_tokens_per_s."""


def read(facts):
    c = facts.get('counters', {})
    prompt = c.get('prefill_prompt_tokens_total')
    if not prompt:
        return None
    return 100.0 * c.get('kv_prefix_tokens_saved_total', 0) / prompt
