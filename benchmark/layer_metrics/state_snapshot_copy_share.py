"""Model step (ops/ssm_ops.py `snapshot_copy` under the named scope
``paddle_tpu:state_snapshot``, dispatched by serving/generate.py
`_move_blocks`). What the snapshot rows cost the device: the seconds of the
device operation `mosaic:state_snapshot_copy` -- a slot's row of a 'row'
pool copied to a spare row where a prefill dispatch ended on a block's edge,
and a spare row copied into a new tenant's at a hit, one DMA a pool -- over
the trace's busy seconds, in percent. A copy moves a row's bytes once in and
once out (15.5 MB a row in `olmo-hybrid-7b-l8`), a decode step ~16 GB: the
share stays far under 1 % unless a change copies more rows than the edges
ask for, or copies a pool to move a row.

A trace without that operation reads 0 where the engine has snapshot rows
(`stats()['state']['snapshots']`) and nothing where it has none (the parent
commit, a model without state layers, an engine that shares no prefix, the
xla tier of a CPU run). Moves serve_tokens_per_s (the copies queue between
the decode steps and take their time from them)."""

OPS = ('mosaic:state_snapshot_copy',)


def read(facts):
    t = facts.get('trace')
    state = facts.get('engine_stats', {}).get('state', {})
    if not t or not t.get('busy_s') or 'snapshots' not in state:
        return None
    seconds = sum(s for name, s in t['op_seconds'].items()
                  if name.startswith(OPS))
    return 100.0 * seconds / t['busy_s']
