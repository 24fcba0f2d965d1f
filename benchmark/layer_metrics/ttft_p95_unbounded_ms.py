"""Server (serving/generate.py). The 95th percentile of time to first
token where it is NOT an end-to-end metric: in a cell whose times to first
token fall into a few modes (one per prefill bucket, plus a queued prefill
ahead) the 95th percentile sits on the edge between two modes and flips
with the order of the requests, so it is recorded here without a bound.
Host clock at the client, as ttft_p95_ms. In a closed loop a shorter time
to first token is more requests a second: moves serve_tokens_per_s."""


def read(facts):
    return facts['end_to_end'].get('ttft_p95_ms')
