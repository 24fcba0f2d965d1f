"""ServingEngine: dynamic batching + bucket warmup + a predictor pool.

The reference framework's inference story stops at the single-request
AnalysisPredictor::Run; a fleet in front of real traffic needs the next
layer up — this module. One in-process engine composes the substrate the
runtime already ships:

- requests land in a BOUNDED `RequestQueue` (batcher.py) and are coalesced
  into dynamically-formed batches (``max_batch_size`` rows or
  ``max_wait_ms``, whichever first);
- every batch is padded onto the `BucketLadder` grid (bucketing.py), so
  steady-state traffic executes a FIXED set of feed signatures — all
  pre-compiled by ``warmup()`` through the PR 1 fingerprint compile cache
  (zero recompiles once warm);
- a pool of worker threads executes batches through the predictor's
  Executor with the per-call ``donate=False`` override (cached params are
  shared by every in-flight batch and must never be consumed), riding the
  executor's ``resilience.RetryPolicy`` at the run boundary: transient
  dispatch faults retry with backoff, exhausted retries surface as
  PER-REQUEST errors — the pool itself never dies;
- per-request deadlines + load shedding give the engine a real
  backpressure story: a full queue rejects with a structured
  `LoadShedError`, an expired request is dropped before it wastes
  accelerator time, and a caller never blocks past its deadline.

Instrumentation (monitor.py): ``serving_request_total{outcome}``
(ok|error|shed|deadline|rejected), ``serving_batch_total``,
``serving_queue_depth`` / ``serving_inflight_batches`` gauges,
``serving_batch_rows`` / ``serving_batch_fill`` / ``serving_queue_seconds``
/ ``serving_execute_seconds`` histograms, and ``serving.batch`` /
``serving.execute`` spans on the monitor ring. Full catalog + tuning
guide: docs/serving.md.
"""
import threading
import time

import numpy as np

from .. import blackbox
from .. import goodput
from .. import monitor
from .. import resilience
from .. import trace as trace_mod
from ..inference import Predictor, PredictorConfig
from .batcher import (ServingError, LoadShedError, DeadlineExceededError,
                      EngineStoppedError, Request, RequestQueue,
                      resolve_metrics_port, start_metrics_server)
from .bucketing import BucketLadder

__all__ = ['ServingConfig', 'ServingEngine', 'create_engine']


class ServingConfig(object):
    """Engine knobs. `model_dir` (or a ready `predictor`) names the model;
    the ladder defaults to power-of-two batch buckets up to
    ``max_batch_size``.

    - max_batch_size: total ROWS a formed batch may carry (the top batch
      bucket).
    - max_wait_ms: how long a forming batch waits for co-riders once its
      first request arrived. 0 disables coalescing delay (latency-first).
    - batch_buckets / seq_buckets / seq_axis / pad_value: the
      `BucketLadder` grid; seq_buckets=None serves fixed-shape models.
    - num_workers: concurrent batch executors (each dispatches through
      the shared predictor; the compile cache and params are shared).
    - queue_cap: bounded-queue depth in REQUESTS; beyond it submissions
      shed with `LoadShedError`.
    - default_deadline_s: per-request deadline when submit() gives none.
    - metrics_port: start a Prometheus ``/metrics`` endpoint
      (``monitor.serve_metrics``) with the engine; 0 binds an ephemeral
      port (read it back from ``engine.metrics_port``), None (default)
      falls back to the ``PADDLE_METRICS_PORT`` env var, and no endpoint
      is started when neither is set.
    - ps_resolver: a ``ps.PSRowResolver`` when the model's embedding
      tables are PS-resident (``ps.psify_predictor``): admission pulls
      the request's rows through the hot-row cache (`ps` trace stage),
      batch formation feeds each ``ps_lookup_table`` site from it — the
      table never fully resides in process, signatures stay fixed.
    - name: stable model name labelling this engine's goodput series
      (defaults to model_dir). A ModelFleet sets it to the fleet-wide
      model name so ``goodput.cost_estimate(name)`` keeps pricing the
      model across hot-swapped versions living in different dirs.
    """

    def __init__(self, model_dir=None, model_filename=None,
                 params_filename=None, max_batch_size=8, max_wait_ms=2.0,
                 batch_buckets=None, seq_buckets=None, seq_axis=1,
                 pad_value=0, num_workers=2, queue_cap=64,
                 default_deadline_s=30.0, metrics_port=None,
                 ps_resolver=None, name=None):
        self.ps_resolver = ps_resolver
        self.model_dir = model_dir
        self.name = name
        self.model_filename = model_filename
        self.params_filename = params_filename
        self.max_batch_size = int(max_batch_size)
        self.max_wait_ms = float(max_wait_ms)
        if batch_buckets is None:
            batch_buckets, b = [], 1
            while b < self.max_batch_size:
                batch_buckets.append(b)
                b *= 2
            batch_buckets.append(self.max_batch_size)
        self.batch_buckets = batch_buckets
        self.seq_buckets = seq_buckets
        self.seq_axis = seq_axis
        self.pad_value = pad_value
        self.num_workers = max(1, int(num_workers))
        self.queue_cap = int(queue_cap)
        self.default_deadline_s = default_deadline_s
        self.metrics_port = metrics_port


class ServingEngine(object):
    """In-process serving engine over one loaded model. ::

        engine = fluid.serving.ServingEngine(
            fluid.serving.ServingConfig('model_dir', max_batch_size=8,
                                        seq_buckets=[32, 64, 128]))
        engine.warmup({'tokens': np.zeros((1, 40), 'int64')})
        with engine:                       # start()/stop()
            out = engine.run({'tokens': ids})        # blocking
            fut = engine.submit({'tokens': ids2})    # concurrent callers
            logits = fut.result()[0]
    """

    def __init__(self, config, predictor=None):
        if isinstance(config, str):
            config = ServingConfig(model_dir=config)
        self.config = config
        if predictor is None:
            predictor = Predictor(PredictorConfig(
                model_dir=config.model_dir,
                model_filename=config.model_filename,
                params_filename=config.params_filename))
        self.predictor = predictor
        # name the program's goodput series NOW: counters exported by a
        # periodic snapshot before the first stats() call would
        # otherwise label as the bare fingerprint and split the series
        try:
            goodput.name_model(predictor.program._fingerprint(),
                               config.name or config.model_dir
                               or 'serving')
        except Exception:       # noqa: BLE001 — telemetry only
            pass
        self.ladder = BucketLadder(config.batch_buckets,
                                   seq_buckets=config.seq_buckets,
                                   seq_axis=config.seq_axis,
                                   pad_value=config.pad_value)
        if self.ladder.max_rows != config.max_batch_size:
            raise ValueError(
                "batch_buckets %r must top out at max_batch_size %d"
                % (config.batch_buckets, config.max_batch_size))
        self.ps_resolver = config.ps_resolver
        self.queue = RequestQueue(config.queue_cap)
        self._workers = []
        self._started = False
        self._lock = threading.Lock()
        self._inflight_n = 0
        self._inflight_lock = threading.Lock()
        self._metrics_server = None
        monitor.set_gauge('serving_queue_depth', 0.0)

    @property
    def metrics_port(self):
        """Bound port of the engine's /metrics endpoint (None when not
        serving metrics — see ServingConfig.metrics_port)."""
        return self._metrics_server.port if self._metrics_server else None

    @property
    def metrics_url(self):
        return self._metrics_server.url if self._metrics_server else None

    def _resolve_metrics_port(self):
        return resolve_metrics_port(self.config.metrics_port)

    # ------------------------------------------------------------------
    # lifecycle
    def start(self):
        with self._lock:
            if self._started:
                return self
            if self.queue.closed:
                raise EngineStoppedError(
                    "a stopped ServingEngine cannot restart — build a "
                    "fresh engine (the queue already failed its callers)")
            self._started = True
            if self._metrics_server is None:
                # a fleet scheduler pointing Prometheus at
                # PADDLE_METRICS_PORT sees every serving_* series
                # without extra wiring
                self._metrics_server = start_metrics_server(
                    self._resolve_metrics_port(), 'ServingEngine')
            for i in range(self.config.num_workers):
                t = threading.Thread(target=self._worker_loop,
                                     name='paddle-serving-%d' % i,
                                     daemon=True)
                t.start()
                self._workers.append(t)
        return self

    def stop(self, timeout_s=10.0):
        """Close the queue (queued requests fail with EngineStoppedError),
        let in-flight batches finish, join the workers."""
        with self._lock:
            self._started = False
        drained = self.queue.close()
        if drained:
            monitor.inc('serving_request_total', drained,
                        labels={'outcome': 'stopped'})
        for t in self._workers:
            t.join(timeout_s)
        self._workers = []
        if self._metrics_server is not None:
            self._metrics_server.close()
            self._metrics_server = None

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # ------------------------------------------------------------------
    # request path
    def submit(self, feed, deadline_s=None, return_numpy=True):
        """Enqueue one request; returns the `Request` future. Raises
        synchronously for feeds the engine can never serve (KeyError for
        name mismatches — Predictor.run's contract — ValueError for
        ladder violations) and `LoadShedError` when the bounded queue is
        full; both count into ``serving_request_total``.

        `return_numpy=False` delivers DEVICE-RESIDENT fetch slices (no
        host sync) for callers that chain results into another device
        computation; the default materializes numpy per request — and
        only this request's rows ever cross to the host (batch padding
        stays on device either way)."""
        names = self.predictor.get_input_names()
        managed = (self.ps_resolver.managed_names
                   if self.ps_resolver is not None else ())
        missing = sorted(n for n in names
                         if n not in feed and n not in managed)
        extra = sorted(k for k in feed if k not in names)
        if missing or extra:
            monitor.inc('serving_request_total',
                        labels={'outcome': 'rejected'})
            raise KeyError(
                "serving feed does not match get_input_names() %s:%s%s"
                % (names, ' missing %s' % missing if missing else '',
                   ' unexpected %s' % extra if extra else ''))
        try:
            n_rows, seq_len, key = self.ladder.request_shape(feed)
        except ValueError:
            monitor.inc('serving_request_total',
                        labels={'outcome': 'rejected'})
            raise
        if deadline_s is None:
            deadline_s = self.config.default_deadline_s
        deadline = (time.monotonic() + deadline_s
                    if deadline_s is not None else None)
        req = Request(feed, n_rows, seq_len, key, deadline,
                      return_numpy=return_numpy)
        # every request is a traced unit of work: stage accounting (the
        # timing breakdown on req.timing) is unconditional; span-level
        # recording and the trace-log line ride head sampling
        req.trace = trace_mod.start('serving')
        if self.ps_resolver is not None:
            # ADMISSION pull: this request's embedding rows enter the
            # hot-row cache now (the 'ps' stage on the request trace),
            # so batch formation assembles rows feeds from cache hits
            try:
                with trace_mod.activate(req.trace):
                    self.ps_resolver.prewarm(feed)
            except Exception as e:     # noqa: BLE001 — per-request error
                monitor.inc('serving_request_total',
                            labels={'outcome': 'error'})
                req.fail(e)
                raise
        try:
            self.queue.put(req)
        except (LoadShedError, EngineStoppedError) as e:
            # finishes the trace with the right outcome (keep-errors: a
            # rejected request is never invisible in the trace log)
            monitor.inc('serving_request_total', labels={
                'outcome': 'shed' if isinstance(e, LoadShedError)
                else 'stopped'})
            req.fail(e)
            raise
        monitor.set_gauge('serving_queue_depth', self.queue.depth())
        return req

    def run(self, feed, deadline_s=None, timeout=None, return_numpy=True):
        """Blocking convenience: submit + result. Returns the fetch list
        (rows sliced back to this request; numpy unless
        return_numpy=False)."""
        return self.submit(feed, deadline_s=deadline_s,
                           return_numpy=return_numpy).result(timeout)

    # ------------------------------------------------------------------
    # warmup
    def warmup(self, example_feed):
        """Compile every ladder cell ahead of traffic by tiling/padding
        `example_feed` (ONE representative row, or any request-shaped
        feed) to each (batch bucket, seq bucket) signature and executing
        it. Steady-state traffic then hits the compile cache only.

        Routes through the process-wide warmup farm
        (paddle_tpu.warmfarm): cells whose signature another engine in
        this process already compiled are SKIPPED outright — the second
        process-sharing consumer of a signature set warms in ~0 s with a
        compile_seconds delta of ≈ 0 (the AOT-reuse contract; the
        executables live in the fingerprint cache, so this engine's
        traffic dispatches them directly).

        Returns {'buckets', 'compiles', 'reused', 'seconds'} where
        `compiles` is the compile_cache_miss delta — on a second warmup
        of the same engine (or a fresh engine over the same model in the
        same process) it is 0, the fingerprint-cache contract."""
        from ..warmfarm import farm
        t0 = time.perf_counter()
        before = monitor.counters()
        arrays = {n: np.asarray(v) for n, v in example_feed.items()}
        _, seq_len, _ = self.ladder.request_shape(arrays)
        cells = 0
        reused = 0
        for bb, sb in self.ladder.bucket_grid():
            feed = {}
            for name, a in arrays.items():
                v = a
                if sb is not None and seq_len is not None and \
                        a.ndim > self.ladder.seq_axis and \
                        a.shape[self.ladder.seq_axis] == seq_len:
                    # stretch/trim the example's seq axis to the bucket
                    take = min(a.shape[self.ladder.seq_axis], sb)
                    sl = [slice(None)] * a.ndim
                    sl[self.ladder.seq_axis] = slice(0, take)
                    v = a[tuple(sl)]
                    if take < sb:
                        pad = [(0, 0)] * a.ndim
                        pad[self.ladder.seq_axis] = (0, sb - take)
                        v = np.pad(v, pad, mode='constant',
                                   constant_values=self.ladder.pad_value)
                n = v.shape[0]
                if n < bb:
                    v = np.concatenate(
                        [v] * (bb // n) + [v[:bb % n]], axis=0)
                elif n > bb:
                    v = v[:bb]
                feed[name] = v
            if self.ps_resolver is not None:
                # rows feeds are part of the compiled signature: resolve
                # BEFORE the farm tracks it, exactly like live dispatch
                feed.update(self.ps_resolver.resolve(feed))
            p = self.predictor
            key, already = farm.track(p.executor, p.program, feed,
                                      fetch_list=p.fetch_vars,
                                      scope=p.scope, donate=False)
            if already:
                # another engine in this process already compiled this
                # cell AND the entry is still cache-resident (track's
                # LRU-eviction guard)
                reused += 1
            else:
                with monitor.span('serving.warmup'):
                    self._execute(feed)
                farm.commit(key)
            cells += 1
        delta = monitor.counter_delta(before)
        compiles = sum(v for k, v in delta.items()
                       if k.startswith('compile_cache_miss'))
        out = {'buckets': cells, 'compiles': int(compiles),
               'reused': reused,
               'seconds': round(time.perf_counter() - t0, 3)}
        monitor.inc('serving_warmup_total')
        monitor.set_gauge('serving_warmup_buckets', cells)
        return out

    # ------------------------------------------------------------------
    # worker pool
    def _execute(self, feed):
        """One batched dispatch through the predictor's executor. Params
        are cached device-side in the predictor's private scope and must
        survive every call: donation is overridden OFF per call (never
        via env — other threads may be training in this process).
        Transient dispatch faults retry inside the executor under the
        'run' site RetryPolicy; what escapes here is either permanent or
        retry-exhausted and becomes a per-request error upstream.

        Fetches stay DEVICE-RESIDENT (return_numpy=False): un-batching
        slices them on device and only each request's own rows are
        materialized at delivery (see _slice_result) — the padded batch
        never round-trips through the host."""
        if self.ps_resolver is not None:
            feed = dict(feed)
            feed.update(self.ps_resolver.resolve(feed))
        p = self.predictor
        return p.executor.run(p.program, feed=feed,
                              fetch_list=p.fetch_vars, scope=p.scope,
                              return_numpy=False, donate=False)

    def _worker_loop(self):
        """Pipelined worker: while batch K executes on the device, this
        thread forms batch K+1 (padding, stacking, bucket math) — the
        executor's async path makes the dispatch non-blocking, the
        worker-local `pending` slot keeps delivery in order. Delivery of
        an in-flight batch is never deferred behind an EMPTY queue: when
        there is nothing to form, the pending batch finishes
        immediately, so a lone request still sees dispatch-latency
        delivery."""
        poll = 0.05
        pending = None
        while True:
            if self.queue.closed and self.queue.depth() == 0:
                if pending is not None:
                    self._finish_batch(pending)
                return
            if pending is not None and self.queue.depth() == 0:
                self._finish_batch(pending)
                pending = None
            batch, expired = self.queue.take_batch(
                self.ladder.max_rows, self.config.max_wait_ms / 1000.0,
                poll_s=poll)
            now = time.monotonic()
            for r in expired:
                monitor.inc('serving_request_total',
                            labels={'outcome': 'deadline'})
                r.fail(DeadlineExceededError(
                    "deadline passed after %.3fs in queue"
                    % (now - r.enqueue_t)))
            if not batch:
                if pending is not None:
                    self._finish_batch(pending)
                    pending = None
                continue
            monitor.set_gauge('serving_queue_depth', self.queue.depth())
            nxt = self._dispatch_batch(batch)
            if pending is not None:
                # batch K+1 is dispatched: finishing K now overlaps its
                # delivery (host-side slicing/materialization) with K+1's
                # device execution
                self._finish_batch(pending)
            pending = nxt

    def _dispatch_batch(self, batch):
        """Form one padded batch and dispatch it asynchronously. Returns
        the pending (future, batch, padded_rows, t0, wall_us) record for
        `_finish_batch`, or None when formation failed (those requests
        are already failed — the pool never dies).

        Trace accounting: each request's 'queue' stage closes here
        (enqueue -> this worker picking it up, co-rider wait included)
        and the shared formation time lands as its 'batch' stage; for
        sampled traces the matching spans are stamped retrospectively —
        the queue span on the SUBMITTER's tid, formation on this
        worker's — so exported traces show the thread hop."""
        with monitor.span('serving.batch'):
            t_form0 = time.perf_counter()
            form_wall = time.time() * 1e6
            now_m = time.monotonic()
            n_rows = sum(r.n_rows for r in batch)
            for r in batch:
                qs = max(0.0, now_m - r.enqueue_t)
                monitor.observe('serving_queue_seconds', qs)
                # queue-SLO burn sentinel (perf_regression_total
                # {kind=queue_burn} once the EWMA burns past
                # PADDLE_PERFWATCH_QUEUE_SLO_MS)
                goodput.note_queue_wait(qs)
                if r.trace is not None:
                    r.trace.add_stage('queue', qs)
                    monitor.record_span('request.queue', r.enqueue_wall,
                                        qs * 1e6, tid=r._tid,
                                        trace=r.trace)
            try:
                padded = [self.ladder.pad_request(r.feed, r.seq_len)
                          for r in batch]
                stacked = {
                    name: np.concatenate([p[name] for p in padded], axis=0)
                    for name in padded[0]}
                stacked, padded_rows = self.ladder.pad_rows(stacked, n_rows)
                if self.ps_resolver is not None:
                    # rows feeds for the PADDED batch (pad-value ids hit
                    # the cache after the first batch of a bucket); the
                    # fed rows shape is a pure function of the bucketed
                    # ids shape, so signatures stay fixed
                    stacked.update(self.ps_resolver.resolve(stacked))
            except Exception as e:      # noqa: BLE001 — delivered per-request
                monitor.inc('serving_batch_error_total')
                blackbox.record('serving_batch_error', error=e,
                                stage='form', requests=len(batch))
                for r in batch:
                    monitor.inc('serving_request_total',
                                labels={'outcome': 'error'})
                    r.fail(e)
                return None
            monitor.observe('serving_batch_rows', n_rows)
            monitor.observe('serving_batch_fill',
                            n_rows / float(padded_rows))
            monitor.inc('serving_batch_total')
            monitor.inc('serving_batch_padded_rows', padded_rows - n_rows)
            form_s = time.perf_counter() - t_form0
            for r in batch:
                if r.trace is not None:
                    r.trace.add_stage('batch', form_s)
                    monitor.record_span('request.batch', form_wall,
                                        form_s * 1e6, trace=r.trace)
            t0 = time.perf_counter()
            monitor.set_gauge('serving_inflight_batches', self._inflight(1))
            p = self.predictor
            # donation stays off per call (shared cached params); faults
            # and retry-exhaustion surface on the future, failed below
            fut = p.executor.run_async(p.program, feed=stacked,
                                       fetch_list=p.fetch_vars,
                                       scope=p.scope, donate=False)
            return (fut, batch, padded_rows, t0, time.time() * 1e6)

    def _finish_batch(self, pending):
        """Wait for a dispatched batch, then deliver per-request slices.
        serving_execute_seconds spans dispatch→device completion (it may
        include host time the worker spent forming the NEXT batch — the
        overlap is the point)."""
        fut, batch, padded_rows, t0, disp_wall = pending
        try:
            try:
                with monitor.span('serving.execute'):
                    # device-resident fetches; result() blocks until the
                    # device completed, so the histogram still measures
                    # completion, not async dispatch
                    outs = fut.result(return_numpy=False)
            finally:
                monitor.set_gauge('serving_inflight_batches',
                                  self._inflight(-1))
            exec_s = time.perf_counter() - t0
            monitor.observe('serving_execute_seconds', exec_s)
            for r in batch:
                if r.trace is not None:
                    r.trace.add_stage('execute', exec_s)
                    monitor.record_span('request.execute', disp_wall,
                                        exec_s * 1e6, trace=r.trace)
        except Exception as e:      # noqa: BLE001 — delivered per-request
            # a failed batch fails ITS requests; the worker and the
            # pool live on (retry-exhausted transients land here too)
            monitor.inc('serving_batch_error_total')
            blackbox.record('serving_batch_error', error=e,
                            stage='execute', requests=len(batch),
                            padded_rows=padded_rows)
            for r in batch:
                if r.trace is not None:
                    r.trace.add_stage('execute',
                                      time.perf_counter() - t0)
                monitor.inc('serving_request_total',
                            labels={'outcome': 'error'})
                r.fail(e)
            return
        # batch-level fetches (no padded leading dim) are shared whole by
        # every request in the batch: materialize them host-side ONCE
        # here, not once per request in _slice_result
        for i, o in enumerate(outs):
            if not (getattr(o, 'ndim', 0) and
                    getattr(o, 'shape', (None,))[0] == padded_rows) \
                    and not isinstance(o, np.ndarray):
                outs[i] = np.asarray(o)
        off = 0
        for r in batch:
            # per-request delivery is individually guarded: one request
            # whose un-batching fails (odd fetch shape) must not strand
            # the rest of the batch or kill the worker — "the pool never
            # dies" covers the un-batch path too
            try:
                t_sync0 = time.perf_counter()
                sync_wall = time.time() * 1e6
                res = self._slice_result(outs, off, r, padded_rows)
                if r.trace is not None:
                    sync_s = time.perf_counter() - t_sync0
                    r.trace.add_stage('sync', sync_s)
                    monitor.record_span('request.sync', sync_wall,
                                        sync_s * 1e6, trace=r.trace)
                r.done(res)
                monitor.inc('serving_request_total',
                            labels={'outcome': 'ok'})
            except Exception as e:      # noqa: BLE001 — delivered per-request
                monitor.inc('serving_request_total',
                            labels={'outcome': 'error'})
                r.fail(e)
            off += r.n_rows

    def _inflight(self, d):
        with self._inflight_lock:
            self._inflight_n += d
            return self._inflight_n

    # ------------------------------------------------------------------
    def stats(self):
        """Engine statistics: queue/inflight state plus the live
        goodput/MFU block for THIS engine's program — device-busy
        seconds, delivered flops/s and utilization restricted to the
        predictor's compiled signatures (the process-wide loss buckets
        and regression log ride along; see paddle_tpu.goodput)."""
        out = {
            'queue_depth': self.queue.depth(),
            'inflight_batches': self._inflight(0),
            'workers': len(self._workers),
            'started': self._started,
        }
        try:
            fp = self.predictor.program._fingerprint()
            goodput.name_model(fp, self.config.name
                               or self.config.model_dir or 'serving')
            out['goodput'] = goodput.stats(fps=[fp])
        except Exception:       # noqa: BLE001 — stats stay best-effort
            out['goodput'] = goodput.stats(fps=[])
        return out

    def _slice_result(self, outs, off, req, padded_rows):
        """Un-batch: slice each fetch back to this request's rows, and
        un-pad sequence columns the bucket added. Fetches without the
        batched leading dim (batch-level scalars) are returned whole, as
        numpy — the worker loop materialized them once for the batch.

        Slicing happens on DEVICE (the executor handed us device-resident
        fetches): padded rows and other requests' rows never cross to the
        host. Only when the request asked for numpy (the default) are its
        own rows materialized — previously every request pulled the whole
        padded batch host-side per fetch."""
        out = []
        for o in outs:
            a = o
            if getattr(a, 'ndim', 0) and a.shape[0] == padded_rows:
                a = a[off:off + req.n_rows]
                if req.seq_len is not None:
                    sb = self.ladder.seq_bucket(req.seq_len)
                    ax = self.ladder.seq_axis
                    if sb is not None and sb != req.seq_len and \
                            a.ndim > ax and a.shape[ax] == sb:
                        sl = [slice(None)] * a.ndim
                        sl[ax] = slice(0, req.seq_len)
                        a = a[tuple(sl)]
            if req.return_numpy and not isinstance(a, np.ndarray):
                # batch-level fetches arrive pre-materialized (worker
                # loop, once per batch) — only this request's own sliced
                # rows cross here
                a = np.asarray(a)
            out.append(a)
        return out


def create_engine(config, predictor=None):
    """Factory mirroring inference.create_predictor."""
    return ServingEngine(config, predictor=predictor)
