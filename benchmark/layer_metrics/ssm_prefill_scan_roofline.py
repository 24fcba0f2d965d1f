"""Kernels (ops/ssm_ops.py `prefill_scan`: the recurrence over one prompt
suffix or chunk, a call a Mamba layer a prefill dispatch). The scan's share
of the time its BYTES would take: the bytes it has to move a second / peak
bytes/s / the share of the traced window it runs in, in percent.

- Bytes (benchmark/flops_jamba.py `ssm_prefill_scan_bytes`): what the ONE
  operation whose time is taken moves, nothing around it. Per real row and
  Mamba layer (ssm_prefill_rows_total, serving/generate.py: a bucket's pad
  rows are not counted) `delta` and `delta * u` in and `y` out (d_inner
  each) and `B`, `C` (mamba_d_state each); per scan the state once in and
  once out. NOT `z` nor `u`: the gate `silu(z)` and the skip `D * u` are
  applied outside the operation, in fusions whose time is not taken
  (ISSUE 43 listed `z`; with it the share read a third too high, PR 43's
  review). The scans are the prefill dispatches (the histogram
  prefill_seconds counts the admissions; a chunked admission has more, so
  the count is taken from ssm_state_resumes_total + the admissions) x
  Mamba layers.
- Time: the device operation `mosaic:ssm_prefill_scan` as the trace prints
  it, over the traced window.

benchmark/peaks.json has no peak for the vector unit, so this is a share of
BYTES, and it reads under what a kernel bound by HBM would: a row of the
scan is d_state x d_inner multiply-adds and as many `exp`, sequential in
the position — the scan is bound by the VPU and the EUP (a 512-row chunk
moves ~33 MB a layer, 40 us at peak, and walks 42 M state entries). What
is left under 100 is the finding a later `perf_opt` of the scan starts
from, not a fault of the reader.

The bytes are the 50 s window's and the time the trace's, and the prefills
are few: ~7.7 dispatches a second of 128 to 512 padded rows in
jamba2-serve-reason128. The trace also begins where the ramp ends, when a
request lasts ~20 s and few have ended yet: it holds FEWER prefills a
second than the window, so the share reads HIGH. At the issue's 3 s (~23
dispatches at best) it read 38.8 and 50.2 % by this count of the bytes; the
cell's traffic file traces 8 s, which read 20.3 and 25.9 % (PR 43, two
seeds) where the decode kernel's calls in the same traces put the steady
share at 17 to 18 % (PERF.md section 6). 105 is four times the reading
away. `reduce_trace.reduce` handing over the counters' deltas over the
traced span, or each operation's call count, would take the sampling out
altogether (PERF.md section 7, ROADMAP M4).

A program with no such operation or counter (the parent commit, a model
without state-space layers, the xla tier, a CPU run), or a configuration
without this family's keys, reads nothing. Moves itl_p95_ms (a prefill
sits in a token gap)."""
from benchmark import flops_jamba

OP = 'mosaic:ssm_prefill_scan'


def read(facts):
    t = facts.get('trace')
    c = facts.get('counters', {})
    rows = c.get('ssm_prefill_rows_total')
    m = facts.get('config', {})
    if not t or not rows or not facts.get('window_s') \
            or 'mamba_d_state' not in m or 'mamba_expand' not in m:
        return None
    seconds = sum(s for name, s in t['op_seconds'].items()
                  if name.startswith(OP))
    if not seconds:
        return None
    admissions = facts.get('histograms', {}).get('prefill_seconds',
                                                 (0, 0.0))[0]
    scans = (admissions + c.get('ssm_state_resumes_total', 0)) \
        * flops_jamba.n_ssm_layers(m)
    need = flops_jamba.ssm_prefill_scan_bytes(m, rows, scans)
    least_share = need / facts['window_s'] \
        / facts['peaks']['hbm_bytes_per_s']
    return 100.0 * least_share / (seconds / t['window_s'])
