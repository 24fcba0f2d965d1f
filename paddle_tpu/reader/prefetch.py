"""Host→device prefetch pipeline: the TPU-native replacement for reader ops.

Reference counterparts: operators/reader/create_py_reader_op.cc +
LoDTensorBlockingQueue (lod_tensor_blocking_queue.h:31) and buffered_reader
(buffered_reader.h:30, double-buffer to GPU). Here: a background thread pulls
numpy batches from a python reader into a bounded queue and eagerly
device_puts them, so the accelerator never waits on host input — the same
double-buffering contract, without graph-visible reader ops.

Lifecycle: every iteration over a `DevicePrefetcher` is one *pass* backed by
one daemon worker. A pass ends when the reader is exhausted, when the
consumer closes it (`close()`, or simply dropping the iterator — an early
``break`` out of the for-loop must never leave a worker parked forever on a
full queue), or when the prefetcher itself is closed. The feed dicts a pass
yields are device-resident `jax.Array`s, which `Executor.run`/`run_async`
pass through without host staging — the composition `train_loop`
(paddle_tpu.pipeline) builds on.
"""
import queue as _queue
import threading
import weakref

import numpy as np

__all__ = ['DevicePrefetcher', 'PyReader']


class _End(object):
    def __init__(self, error=None):
        self.error = error


def device_of(place):
    """Resolve a framework Place (CPUPlace/TPUPlace/CUDAPlace), an actual
    jax Device, or None (default device) to what `jax.device_put` wants."""
    if place is None:
        return None
    if hasattr(place, 'platform'):          # already a jax Device
        return place
    import jax
    from ..framework import CPUPlace
    try:
        devs = jax.devices('cpu') if isinstance(place, CPUPlace) \
            else jax.devices()
    except RuntimeError:
        # backend absent (JAX_PLATFORMS left 'cpu' unregistered): fall
        # back to the default device rather than refusing to stage
        devs = jax.devices()
    idx = getattr(place, 'device_id', 0)
    return devs[idx] if 0 <= idx < len(devs) else devs[0]


class _PrefetchIter(object):
    """One live prefetch pass: a daemon worker pulls batches from the
    reader, stages them onto the device, and hands them over a bounded
    queue. `close()` cancels the pass: it unblocks a worker parked on the
    full queue (the put is a timed poll against the stop event, never an
    unbounded block) and retires it. Dropping the iterator without
    closing triggers the same cancellation from ``__del__``."""

    _POLL_S = 0.05

    def __init__(self, owner):
        import jax
        self._q = _queue.Queue(maxsize=owner._capacity)
        self._stop = threading.Event()
        self._finished = False
        reader = owner._reader
        feeder = owner._feeder
        feed_names = owner._feed_names
        device = device_of(owner._device)
        stop, q, poll = self._stop, self._q, self._POLL_S

        def _stage(v):
            if isinstance(v, jax.Array):
                return v                    # already device-resident
            if isinstance(v, tuple) and len(v) == 2 and \
                    isinstance(v[1], (list, tuple)):
                # (array, lod) ragged feed — the executor's
                # _split_lod_feed convention: stage values, keep the LoD
                return (jax.device_put(np.asarray(v[0]), device), v[1])
            if isinstance(v, (tuple, list)):
                # structural batch (double_buffer over a tuple reader):
                # stage the leaves, keep the shape
                return type(v)(_stage(e) for e in v)
            return jax.device_put(np.asarray(v), device)

        def _put(item):
            # bounded put that gives up once the consumer went away
            while not stop.is_set():
                try:
                    q.put(item, timeout=poll)
                    return True
                except _queue.Full:
                    continue
            return False

        def worker():
            try:
                for batch in reader():
                    if stop.is_set():
                        return
                    if feeder is not None:
                        feed = feeder.feed(batch)
                    elif isinstance(batch, dict):
                        feed = batch
                    elif feed_names is not None:
                        feed = dict(zip(feed_names, batch))
                    else:
                        # nameless non-dict batch (a double_buffer'd
                        # tuple/array reader): stage structurally
                        if not _put(_stage(batch)):
                            return
                        continue
                    # eager device_put = transfer overlaps with compute
                    feed = {k: _stage(v) for k, v in feed.items()}
                    if not _put(feed):
                        return
            except BaseException as e:      # surfaced on the consumer
                _put(_End(e))
            else:
                _put(_End())

        self._thread = threading.Thread(target=worker, daemon=True,
                                        name='paddle-prefetch')
        self._thread.start()

    def __iter__(self):
        return self

    def __next__(self):
        if self._finished:
            raise StopIteration
        while True:
            try:
                item = self._q.get(timeout=self._POLL_S)
                break
            except _queue.Empty:
                if self._stop.is_set():
                    self._finished = True
                    raise StopIteration
                if not self._thread.is_alive():
                    # the worker exited — but it may have put its last
                    # batch (or the _End sentinel) between our timeout
                    # and this liveness check, so drain once more before
                    # giving up; a dead worker enqueues nothing further,
                    # so the nowait read is race-free
                    try:
                        item = self._q.get_nowait()
                        break
                    except _queue.Empty:
                        # genuinely died without a sentinel — never hang
                        self._finished = True
                        raise StopIteration
        if isinstance(item, _End):
            self._finished = True
            if item.error is not None:
                raise item.error
            raise StopIteration
        return item

    next = __next__                         # py2-style callers

    def close(self, timeout_s=2.0):
        """Cancel the pass: stop the worker (draining the queue so a
        blocked put observes the stop event) and join it."""
        self._finished = True
        self._stop.set()
        while True:
            try:
                self._q.get_nowait()
            except _queue.Empty:
                break
        t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout_s)

    def __del__(self):
        try:
            self._stop.set()                # no join in a finalizer
        except Exception:
            pass


class DevicePrefetcher(object):
    """Iterate device-resident feed dicts from a batch reader.

    Each ``iter(prefetcher)`` starts one background pass (a fresh run of
    ``reader()``); `close()` cancels every live pass — consumers that
    abandon iteration early (``break``) are also covered by iterator
    finalization, so no worker thread is ever left blocked on the bounded
    queue. Context-manager use closes on exit."""

    def __init__(self, reader, feed_names=None, capacity=2, device=None,
                 feeder=None):
        self._reader = reader
        self._feed_names = feed_names
        self._capacity = max(1, int(capacity))
        self._device = device
        self._feeder = feeder
        self._passes = []                   # weakrefs to live passes

    @property
    def capacity(self):
        return self._capacity

    def __call__(self):
        """Callable-reader convention (`for batch in reader():`), so a
        prefetch stage composes anywhere a batch reader is accepted —
        each call is one fresh pass."""
        return iter(self)

    def __iter__(self):
        it = _PrefetchIter(self)
        live = []
        for r in self._passes:
            p = r()
            if p is not None and not p._finished:
                live.append(r)
        live.append(weakref.ref(it))
        self._passes = live
        return it

    def close(self, timeout_s=2.0):
        """Cancel every live prefetch pass (unblocks and retires their
        worker threads). Idempotent; the prefetcher can be iterated again
        afterwards (a new pass starts from the reader's beginning)."""
        passes, self._passes = self._passes, []
        for r in passes:
            p = r()
            if p is not None:
                p.close(timeout_s)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class PyReader(object):
    """API-parity shim for fluid.layers.py_reader usage patterns
    (reference layers/io.py:636): decorate with a paddle reader, then
    drive the documented epoch lifecycle::

        reader.decorate_sample_list_generator(train_reader)
        for epoch in range(n):
            reader.start()                  # begin prefetching this epoch
            for feed in reader:             # consume it
                exe.run(main, feed=feed, ...)
            reader.reset()                  # retire it; start() again

    `start()` launches the epoch's prefetch worker; iterating consumes
    that same epoch (a bare ``for feed in reader:`` without `start()`
    starts one implicitly — and a bare loop after natural exhaustion
    starts the next epoch, so nested epoch/batch loops need no explicit
    lifecycle calls at all); `reset()` cancels the in-flight epoch —
    including its worker thread, even mid-epoch — so the next `start()`
    re-reads the data source from the beginning."""

    def __init__(self, feed_list=None, capacity=2, use_double_buffer=True,
                 iterable=True):
        from ..framework import Variable
        # keep the Variables themselves: resolving bare names later against
        # default_main_program would break when another program is current
        self._feed_vars = [v for v in (feed_list or [])
                           if isinstance(v, Variable)]
        self._feed_names = [v.name if isinstance(v, Variable) else v
                            for v in (feed_list or [])]
        self._capacity = capacity
        self._prefetcher = None
        self._iter = None

    @staticmethod
    def _place(places):
        # accept a bare Place as well as the reference's list-of-places
        return places[0] if isinstance(places, (list, tuple)) else places

    def decorate_sample_list_generator(self, reader, places=None):
        from ..data_feeder import DataFeeder
        feeder = DataFeeder(self._feed_vars or self._feed_names)
        self._prefetcher = DevicePrefetcher(reader, capacity=self._capacity,
                                            feeder=feeder,
                                            device=self._place(places))
        return self

    def decorate_batch_generator(self, reader, places=None):
        self._prefetcher = DevicePrefetcher(reader,
                                            feed_names=self._feed_names,
                                            capacity=self._capacity,
                                            device=self._place(places))
        return self

    decorate_paddle_reader = decorate_sample_list_generator

    def start(self):
        """Begin prefetching one epoch. Raises if no data source is
        decorated yet, or if a started epoch was neither exhausted nor
        reset (the reference blocking-queue contract)."""
        if self._prefetcher is None:
            raise ValueError(
                "PyReader has no data source — call "
                "decorate_sample_list_generator / "
                "decorate_batch_generator first")
        if self._iter is not None and not self._iter._finished:
            raise RuntimeError(
                "PyReader.start(): the previous epoch is still active — "
                "exhaust it or call reset() first")
        self._iter = iter(self._prefetcher)
        return self

    def reset(self):
        """Cancel the in-flight epoch (retiring its prefetch worker, even
        when the consumer stopped mid-epoch) so `start()` can re-read the
        data source from the beginning."""
        it, self._iter = self._iter, None
        if it is not None:
            it.close()

    def __iter__(self):
        # a bare for-loop starts an epoch implicitly — including a FRESH
        # one after natural exhaustion (the pre-PR-7 shim allowed
        # `for epoch ...: for feed in reader:`; silently yielding zero
        # batches on epoch 2 would be a trap). start() after an
        # un-exhausted epoch still raises — that path needs reset().
        if self._iter is None or self._iter._finished:
            self.start()
        return self._iter

    def close(self):
        """Alias of reset() for context-manager-style teardown."""
        self.reset()
