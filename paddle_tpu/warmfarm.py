"""Warmup farm: pre-compile a signature set once per process and share it.

The compile-time tail is the serving fleet's cold-start tax (bert_base hit
162 s in BENCH_r05). The persistent on-disk cache
(docs/executor_performance.md) shortens it across processes; the lever
inside one process is AOT reuse:
``Executor.precompile`` lowers + compiles an entry keyed by the SAME
fingerprint cache ``run()`` uses, and this module keeps the process-wide
ledger of which (program fingerprint, feed signature, fetch set, donate)
keys are already warm. Every ServingEngine / GenerateEngine ``warmup()``
routes through the farm:

- the FIRST consumer of a signature set pays the compiles and registers
  each key;
- every later consumer in the process (another engine over the same
  model, another worker thread, an A/B replica) sees its cells already
  warm and skips them — ``compile_seconds`` delta ≈ 0 and
  ``compile_cache_miss`` delta 0, the reuse contract
  tests/test_warmfarm.py asserts.

CLI twin: ``tools/warmfarm.py`` pre-compiles a model directory's bucket
grid before traffic and prints the per-signature compile seconds next to
the second-pass (reused) timings.

Counters (docs/observability.md): ``warmfarm_signature_total{outcome}``
(compiled|reused), plus the executor's ``precompile_total`` /
``compile_cache_hit`` / ``compile_cache_miss`` /``compile_seconds``.
"""
import threading
import time

from . import monitor

__all__ = ['WarmFarm', 'farm']


class WarmFarm(object):
    """Process-wide ledger of warmed compile-cache keys. Thread-safe:
    engine warmups and worker threads may race; a key is registered after
    its compile completes, so a racing duplicate pays at worst one extra
    cache hit, never a recompile."""

    def __init__(self):
        self._lock = threading.Lock()
        self._keys = {}                # key -> register wall time

    # ------------------------------------------------------------------
    def signature(self, executor, program, feed, fetch_list=None,
                  scope=None, donate=None):
        """The executor compile-cache key this (program, feed, fetch,
        donate) run would use — `Executor._entry_key`, the recipe of
        run()/bind() itself, so the farm's ledger and the cache can never
        disagree."""
        from .executor import _feed_from_spec, global_scope
        if scope is None:
            scope = global_scope()
        feed2, fetch_names, static_feed, static_lods = \
            executor._prepare_run_inputs(program, _feed_from_spec(feed),
                                         scope, fetch_list, count=False)
        return executor._entry_key(program, feed2, static_lods, static_feed,
                                   fetch_names, donate, record=False)

    def is_warm(self, key):
        with self._lock:
            return key in self._keys

    def track(self, executor, program, feed, fetch_list=None, scope=None,
              donate=None):
        """The shared warm-check protocol every engine warmup uses:
        compute the signature key, apply the LRU-eviction guard (a
        ledger entry whose compiled executable was evicted is NOT warm),
        and count the reuse. Returns (key, already_warm); callers that
        go on to compile must follow with :meth:`commit`."""
        key = self.signature(executor, program, feed,
                             fetch_list=fetch_list, scope=scope,
                             donate=donate)
        already = self.is_warm(key) and \
            executor._cache_get(key) is not None
        if already:
            monitor.inc('warmfarm_signature_total',
                        labels={'outcome': 'reused'})
        return key, already

    def commit(self, key):
        """Record a signature the caller just compiled (register + the
        'compiled' outcome — also on a re-stamp after LRU eviction,
        which IS a compile, not a reuse)."""
        self.register(key)
        monitor.inc('warmfarm_signature_total',
                    labels={'outcome': 'compiled'})

    def register(self, key):
        """Stamp (or re-stamp) a key in the ledger; returns whether it
        was new. Pure bookkeeping — outcome counters belong to the
        CALLER, which knows whether it actually compiled or reused (a
        re-stamp after an LRU-eviction recompile is a compile, not a
        reuse)."""
        with self._lock:
            fresh = key not in self._keys
            self._keys[key] = time.time()
        return fresh

    def size(self):
        with self._lock:
            return len(self._keys)

    # ------------------------------------------------------------------
    def warm(self, executor, program, feeds, fetch_list=None, scope=None,
             donate=None):
        """Precompile every feed signature in ``feeds`` (an iterable of
        feed dicts; values may be arrays or (shape, dtype) specs) that is
        not already farm-warm. Returns {'signatures', 'compiled',
        'reused', 'seconds'}."""
        from .executor import _feed_from_spec
        t0 = time.perf_counter()
        compiled = reused = 0
        for feed in feeds:
            feed = _feed_from_spec(feed)
            key, already = self.track(executor, program, feed,
                                      fetch_list=fetch_list, scope=scope,
                                      donate=donate)
            if already:
                reused += 1
                continue
            executor.precompile(program, feed, fetch_list=fetch_list,
                                scope=scope, donate=donate)
            self.commit(key)
            compiled += 1
        return {'signatures': compiled + reused, 'compiled': compiled,
                'reused': reused,
                'seconds': round(time.perf_counter() - t0, 3)}


#: the process singleton every engine warmup routes through
farm = WarmFarm()
