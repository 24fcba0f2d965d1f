"""Kernels (ops/paged_decode_attention.py under a looped model,
`LMConfig.passes`: a call a layer a PASS, each against its own cache layer).
The kernel's share of its roofline, which is HBM: the K and V bytes its
calls INSIDE THE TRACE had to read / the seconds they ran there / peak
bytes/s, in percent. `paged_decode_attention_roofline` for a looped model's
cell (that reader's list is held whole: PERF.md section 7, M7).

- Bytes (benchmark/flops_ouro.py `paged_decode_attention_bytes`): the rows
  that the decode steps dispatched UNDER THE PROFILER'S SESSION read --
  `stats()['passes']['traced']['kv_tokens_read_total']`
  (serving/generate.py: per such step the live positions of the active
  slots x the pool's CACHE layers, passes x layers) x K and V of the K/V
  heads (2 x num_key_value_heads x head_dim x 4). The kernel copies whole
  pages, a slot's last one too: what it moves beyond the live positions is
  its overhead, and lowers this share.
- Time: the device operations `mosaic:paged_decode_attention*` as the trace
  prints them (a looped model's calls carry their pass:
  `..._loop_pass_<t>`), summed over the traced span.

Both sides are the trace's own, so neither a stall of the machine's host
inside the trace nor a window whose contexts are longer than the trace's
moves it (the accepted rooflines divide the WINDOW's counters by a share of
the trace: PERF.md section 7, after PR 55, h -- in this cell the trace is
taken while the first requests are still young, and the window's K/V a step
is a tenth above the trace's). What is left: the pipeline is a step deep, so
a step dispatched under the session may run after it, and the first step of
the trace was dispatched before it -- a step in ~450 either way.

A program with no such operation or tally (the parent commit, a one-pass
model, a CPU run), or a configuration without this family's keys, reads
nothing. Moves serve_tokens_per_s (the K/V walk is a third of the step that
gives every slot its token)."""
from benchmark import flops_ouro

OP = 'mosaic:paged_decode_attention'
KEYS = ('total_ut_steps', 'num_key_value_heads', 'head_dim')


def read(facts):
    t = facts.get('trace')
    m = facts.get('config', {})
    traced = ((facts.get('engine_stats') or {}).get('passes')
              or {}).get('traced') or {}
    rows = traced.get('kv_tokens_read_total')
    if not t or not rows or any(k not in m for k in KEYS):
        return None
    seconds = sum(s for name, s in t['op_seconds'].items()
                  if name.startswith(OP))
    if not seconds:
        return None
    return 100.0 * flops_ouro.paged_decode_attention_bytes(m, rows) \
        / seconds / facts['peaks']['hbm_bytes_per_s']
