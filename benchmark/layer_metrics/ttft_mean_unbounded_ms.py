"""Server (serving/generate.py). The mean time to first token over every
request of the window, where no statistic of it is steady enough to carry
a bound (see ttft_p95_unbounded_ms: the mean moved by +-4 % with the order
of the requests alone). Host clock at the client. Moves
serve_tokens_per_s."""


def read(facts):
    return facts['end_to_end'].get('ttft_mean_ms')
