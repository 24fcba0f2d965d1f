"""Server (serving/generate.py `_paged_plan`, serving/kv_blocks.py
`PrefixCache`'s side and `WindowRings.resume`). Of the window's
admissions, the share that RESUMED at a shared prefix's edge over the
window layers -- their rings given the prefix's last `sliding_window - 1`
rows as the prefix cache kept them, nothing of the prefix prefilled again:
kv_window_prefix_resumes_total / generate_admit_total, both as they moved
over the measured window, in percent. Traffic whose every request starts
with one shared prefix reads ~100; a change that loses the prefix's
window blocks (an eviction order that takes the deepest first, a ring that
writes over a shared block) turns every hit into a miss or a shallower
hit, and a miss reads 0 here while the global layers' cache still hits.

A program whose `stats()` counts no window blocks that the prefix cache
holds (the parent commit, a model without window layers, an engine that
shares no prefix) or a window without an admission reads nothing; one that
does and resumed nowhere reads 0. Moves itl_p95_ms (an admission that resumes costs the other
streams 1-4 chunks' time, one that does not costs them 17-20)."""


def read(facts):
    c = facts.get('counters', {})
    admitted = c.get('generate_admit_total')
    window = facts.get('engine_stats', {}).get('blocks', {}).get('window', {})
    if not admitted or 'cached' not in window:
        return None
    return 100.0 * c.get('kv_window_prefix_resumes_total', 0) / admitted
