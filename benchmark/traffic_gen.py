"""The one general traffic generator. A traffic mix is a data file under
benchmark/traffic/; everything here is looked up by the names in it.

Lengths: every seed gets THE SAME SET of requests — the `pool_size`
stratified quantiles of the named distributions, paired once by the mix —
in another order, so a seed changes the order of the work and not the
work. A closed loop hands request i to client i % clients, which cycles
through its share of the pool; with pool_size = clients every client
repeats one request, and what is in flight is the whole pool at any time.

Arrivals: `closed` (N clients, each sends its next request when the last
token of the previous one arrived) and `open` (requests are due on a
seeded schedule whether or not earlier ones finished; time to first token
counts from the due instant, and the run reports how late the generator
ran).

The system under test is reached through one callable,
`submit(prompt, max_new_tokens) -> handle` with `handle.stream()` yielding
tokens and `handle.finish_reason` set afterwards; the generator never
looks inside it.
"""
import statistics
import threading
import time

import numpy as np

# seeds go a little over 2**31; numpy's legacy RandomState takes 32 bits
_SEED_MOD = 2 ** 32


def _rng(seed, stream=0):
    return np.random.default_rng([int(seed) % _SEED_MOD, int(stream)])


# --------------------------------------------------------------------------
# length distributions, by name

def _quantiles(n):
    return (np.arange(n) + 0.5) / n


def _lognormal(spec, n):
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf(q) for q in _quantiles(n)])
    return np.exp(np.log(spec['median']) + spec['sigma'] * z)


def _uniform(spec, n):
    return spec['min'] + _quantiles(n) * (spec['max'] - spec['min'])


def _fixed(spec, n):
    return np.full(n, spec['value'], dtype=float)


DISTRIBUTIONS = {'lognormal': _lognormal, 'uniform': _uniform,
                 'fixed': _fixed}


def length_pool(spec, n):
    """The n stratified quantiles of the distribution `spec` names, as
    whole numbers clipped to [min, max] where the spec gives them."""
    vals = np.rint(DISTRIBUTIONS[spec['dist']](spec, n)).astype(np.int64)
    lo = spec.get('min', spec.get('value', 1))
    hi = spec.get('max', spec.get('value', None))
    return np.clip(vals, lo, hi)


def make_requests(traffic, vocab_size, seed):
    """`pool_size` requests: [{'prompt': int64 array, 'max_new_tokens': n,
    'group': g}]. Every seed gets the same set of (prompt length, output
    length) PAIRS — which output length goes with which prompt length is
    fixed by the mix (`pairing_seed`, default 0), because a long prompt
    paired with a short answer is prefilled more often than one paired
    with a long answer: another pairing is other work. The seed orders the
    pairs and draws the tokens, uniform over [1, vocab). In a closed loop
    client c sends requests c, c + clients, ...: the pairs, ascending in
    prompt length, are dealt round the clients like cards, so each client's
    share spans the whole range and every client has the same work a cycle
    whatever the seed; the seed decides which client gets which share and
    the order inside it. With
    `shared_prefix_len` and `group_size` > 0, consecutive requests form
    groups that share their first `shared_prefix_len` tokens."""
    n = int(traffic['pool_size'])
    pairing = _rng(traffic.get('pairing_seed', 0), 4)
    plen = length_pool(traffic['prompt_len'], n)
    olen = pairing.permutation(length_pool(traffic['output_len'], n))
    rng = _rng(seed, 1)
    arrival = traffic.get('arrival', {})
    lanes = int(arrival['clients']) if arrival.get('kind') == 'closed' else 1
    if n % lanes:
        raise ValueError('pool_size %d is not a multiple of %d clients'
                         % (n, lanes))
    order = np.empty(n, dtype=np.int64)
    for lane, share in zip(rng.permutation(lanes), range(lanes)):
        order[lane::lanes] = rng.permutation(np.arange(share, n, lanes))
    plen, olen = plen[order], olen[order]
    shared = int(traffic.get('shared_prefix_len', 0))
    gsize = int(traffic.get('group_size', 0))
    prefixes = {}
    out = []
    for i in range(n):
        toks = rng.integers(1, vocab_size, size=int(plen[i]), dtype=np.int64)
        group = i // gsize if shared and gsize else None
        if group is not None:
            if group not in prefixes:
                prefixes[group] = rng.integers(1, vocab_size, size=shared,
                                               dtype=np.int64)
            k = min(shared, len(toks))
            toks[:k] = prefixes[group][:k]
        out.append({'prompt': toks, 'max_new_tokens': int(olen[i]),
                    'group': group})
    return out


def train_batches(seed, sequences, seq_len, vocab_size):
    """Endless host-side generator of {'tokens', 'labels'} int64 batches,
    uniform over the vocabulary, a fresh batch every step."""
    rng = _rng(seed, 2)
    while True:
        yield {'tokens': rng.integers(0, vocab_size, (sequences, seq_len),
                                      dtype=np.int64),
               'labels': rng.integers(0, vocab_size, (sequences, seq_len),
                                      dtype=np.int64)}


def open_schedule(arrival, n, seed):
    """Due instants (seconds from the start) of n requests: bursts of
    `burst` requests, the bursts a Poisson process at rate_rps / burst."""
    burst = max(1, int(arrival.get('burst', 1)))
    n_bursts = -(-n // burst)
    gaps = _rng(seed, 3).exponential(burst / float(arrival['rate_rps']),
                                     n_bursts)
    return np.repeat(np.cumsum(gaps), burst)[:n]


# --------------------------------------------------------------------------
# the load loop

class Record(object):
    """One request as the client saw it (perf_counter seconds)."""
    __slots__ = ('t_due', 't_send', 'token_t', 't_end', 'error',
                 'finish_reason', 'asked', 'tokens', 'index')

    def __init__(self, index, asked, t_due=None):
        self.index = index
        self.asked = asked
        self.t_due = t_due
        self.t_send = None
        self.token_t = []
        self.tokens = []
        self.t_end = None
        self.error = None
        self.finish_reason = None

    @property
    def ok(self):
        return (self.error is None and self.finish_reason != 'cache_full'
                and len(self.token_t) == self.asked)


class Load(object):
    """Runs one arrival pattern against `submit`. start() begins sending;
    wait_ramped() returns once the ramp is over; stop() ends the sending
    (the caller then stops the system, which ends requests in flight) and
    join() waits for every thread. `records` holds every request sent."""

    def __init__(self, arrival, requests, submit, seed, clock=None):
        self.arrival = arrival
        self.requests = requests
        self.submit = submit
        self.seed = seed
        self.clock = clock or time.perf_counter
        self.records = []
        self.lateness_s = []
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._threads = []
        self._ready = []

    # one request, on the calling thread
    def _one(self, index, t_due=None, ready=None):
        req = self.requests[index % len(self.requests)]
        rec = Record(index, req['max_new_tokens'], t_due)
        with self._lock:
            self.records.append(rec)
        rec.t_send = self.clock()
        try:
            handle = self.submit(req['prompt'], req['max_new_tokens'])
            for tok in handle.stream():
                rec.token_t.append(self.clock())
                rec.tokens.append(int(tok))
                if ready is not None:
                    ready.set()
            rec.finish_reason = getattr(handle, 'finish_reason', None)
        except Exception as e:          # a failed request is data
            rec.error = '%s: %s' % (type(e).__name__, e)
        rec.t_end = self.clock()
        if ready is not None:
            ready.set()

    def _closed_client(self, c, clients, ready):
        time.sleep(c * float(self.arrival.get('stagger_s', 0.0)))
        i = c
        while not self._stop.is_set():
            self._one(i, ready=ready)
            i += clients

    def _open_dispatch(self, ready):
        due = open_schedule(self.arrival, len(self.requests), self.seed)
        t0 = self.clock()
        for i, d in enumerate(due):
            wait = t0 + d - self.clock()
            if wait > 0 and self._stop.wait(wait):
                break
            if self._stop.is_set():
                break
            self.lateness_s.append(self.clock() - (t0 + d))
            th = threading.Thread(target=self._one, args=(i, t0 + d, ready),
                                  name='bench-open-%d' % i, daemon=True)
            with self._lock:
                self._threads.append(th)
            th.start()

    def start(self):
        kind = self.arrival['kind']
        if kind == 'closed':
            n = int(self.arrival['clients'])
            for c in range(n):
                ev = threading.Event()
                self._ready.append(ev)
                self._threads.append(threading.Thread(
                    target=self._closed_client, args=(c, n, ev),
                    name='bench-client-%d' % c, daemon=True))
        elif kind == 'open':
            ev = threading.Event()
            self._ready.append(ev)
            self._threads.append(threading.Thread(
                target=self._open_dispatch, args=(ev,),
                name='bench-open-dispatch', daemon=True))
        else:
            raise ValueError('unknown arrival kind %r' % kind)
        for th in list(self._threads):
            th.start()
        return self

    def wait_ramped(self, timeout_s=120.0):
        """Closed loop: every client has had the first token of a request.
        Open loop: the first request has had its first token."""
        deadline = time.monotonic() + timeout_s
        for ev in self._ready:
            if not ev.wait(max(0.0, deadline - time.monotonic())):
                raise RuntimeError('traffic did not ramp up in %.0f s'
                                   % timeout_s)

    def stop(self):
        self._stop.set()

    def join(self, timeout_s=60.0):
        deadline = time.monotonic() + timeout_s
        while True:
            with self._lock:
                alive = [t for t in self._threads if t.is_alive()]
            if not alive:
                return
            if time.monotonic() > deadline:
                raise RuntimeError('%d load threads still alive'
                                   % len(alive))
            alive[0].join(0.2)


# --------------------------------------------------------------------------
# from records to the end-to-end numbers

def percentile(values, q):
    """The q-th percentile by nearest rank (the smallest value with at
    least q% of the sample at or below it)."""
    srt = sorted(values)
    if not srt:
        return None
    k = max(0, int(np.ceil(q / 100.0 * len(srt))) - 1)
    return srt[k]


def window_stats(records, t0, t1):
    """What the window [t0, t1] saw, over ALL its requests.

    tokens     every token that reached a client inside the window, whoever
               sent the request and whether or not it finished;
    ttft_s     every request sent (open loop: due) inside the window whose
               first token arrived inside it, timed from the send (due);
    itl_s      every gap between two successive tokens of one request that
               both arrived inside the window;
    attempted  requests sent inside the window that ended inside it, of
               which `failed` errored, were shed, ended cache_full or came
               back short. A request still in flight when the window
               closes is neither (the run cuts it), but its tokens, its
               first token and its gaps inside the window all count: a
               tail is the tail of everything the window saw."""
    tokens = sum(1 for r in records for t in r.token_t if t0 <= t <= t1)
    ttft, itl = [], []
    for r in records:
        start = r.t_due if r.t_due is not None else r.t_send
        if start is not None and start >= t0 and r.token_t \
                and r.token_t[0] <= t1:
            ttft.append(r.token_t[0] - start)
        itl += [b - a for a, b in zip(r.token_t, r.token_t[1:])
                if a >= t0 and b <= t1]
    done = [r for r in records
            if r.t_send is not None and r.t_send >= t0
            and r.t_end is not None and r.t_end <= t1]
    bad = [r for r in done if not r.ok]
    return {'window_s': t1 - t0, 'tokens': tokens,
            'attempted': len(done), 'failed': len(bad),
            'errors': sorted({r.error or r.finish_reason or 'short'
                              for r in bad})[:5],
            'ttft_s': ttft, 'itl_s': itl}
