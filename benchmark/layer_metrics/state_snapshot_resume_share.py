"""Server (serving/generate.py `_paged_plan` / `_publish`, serving/
kv_blocks.py `SlotRows`' snapshot rows and `PrefixCache`'s side). Of the
window's admissions, the share that RESUMED from a snapshot row -- the
recurrent state and convolution tails of every state layer as a prefill
dispatch left them at a shared prefix's block edge, copied into the new
tenant's row, the K/V blocks up to the edge shared, nothing of the prefix
prefilled again: state_snapshot_resumes_total / generate_admit_total, both
as they moved over the measured window, in percent. Traffic whose requests
each start with one of a few hot documents reads ~100 less the documents'
first readers; a change that loses the rows (an eviction order that takes
the deepest edge first, a chain entry that drops its side, a pool too small
for the documents' edges) turns a hit into a shallower hit or a miss of
every chunk, and a miss reads 0 here while `kv_prefix_hit_total` may still
count a matched chain.

A program whose `stats()` has no snapshot rows (the parent commit, a model
without state layers, an engine that shares no prefix) or a window without
an admission reads nothing; one that has them and resumed nowhere reads 0.
Moves serve_tokens_per_s (an admission that resumes holds the 32 streams
for one chunk's time, one that does not for seven: in a closed loop the
difference is tokens a second; the cell reports no itl_p95_ms, PERF.md
section 7)."""


def read(facts):
    c = facts.get('counters', {})
    admitted = c.get('generate_admit_total')
    state = facts.get('engine_stats', {}).get('state', {})
    if not admitted or 'snapshots' not in state:
        return None
    return 100.0 * c.get('state_snapshot_resumes_total', 0) / admitted
