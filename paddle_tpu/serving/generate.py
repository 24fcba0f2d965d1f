"""Continuous-batching generative decode engine with a device-resident
KV cache.

The PR 4 `ServingEngine` batches fixed-signature SINGLE-CALL predictors:
work is admitted at batch boundaries, so decode throughput of an
autoregressive model is bounded by the slowest sentence in each batch.
This module is the decode-native path:

- **Persistent device-resident KV cache.** One pair of persistable
  ``[num_blocks, layers, block_size, heads * head_dim]`` buffers
  (models/transformer.py ``KV_CACHE_K``/``KV_CACHE_V``; the attention
  layers and the K/V heads only, where a model says so, and beside them
  the convolution layers' tails under the same block ids: ``cache_pools``)
  — a pool of fixed-size blocks — lives in the engine's scope like any
  other executor state: the decode step reads AND writes them, so the PR 1
  donation path aliases each step's update in place — the cache never
  doubles in HBM and never crosses the host.
- **Two compiled signatures, fixed forever.** A per-prompt-bucket
  ``prefill`` (prompt lengths pad onto ``prompt_buckets``, the
  reader/bucketing ladder idiom) and ONE single-token ``decode step``
  over all slots. ``warmup()`` compiles every cell through the PR 1
  fingerprint cache; steady-state traffic of ANY prompt/output-length mix
  re-executes exactly that set — ``recompiles_after_warmup = 0``.
- **In-flight (continuous) batching.** New requests are admitted into
  free cache slots at TOKEN boundaries — between decode steps — and
  finished / deadline-expired requests are evicted per step, so a long
  generation never holds short ones hostage. Every op in the step program
  is slot-row-independent (ops/kv_cache_ops.py), so co-residents never
  perturb each other's numerics: tests/test_generate.py pins exact parity
  between concurrent and sequential execution.
- **Streaming responses.** Each `GenerateRequest` is a future AND a token
  stream (``for tok in req.stream()``); per-request deadlines ride the
  PR 4 bounded `RequestQueue` (structured `LoadShedError` backpressure)
  and are enforced both in the queue and mid-generation.

Dispatch rides `Executor.bind` (PR 6): the per-token host tax is state
staging + one compiled call, with fault injection and retry at the 'run'
site exactly as `Executor.run` (a transient fault retries inside the
step; an exhausted retry fails the RESIDENT requests and the engine keeps
serving).

THE BLOCK POOL (PR 12). No slot reserves ``max_len`` rows: each slot
addresses the pool through a runtime-fed block table, so HBM is
committed as sequences actually grow — admission is a blocks-available
decision (serving/kv_blocks.py), eviction returns blocks, and a pool
that runs dry finishes the starved request with
``finish_reason='cache_full'``. On top of the allocator rides PREFIX
SHARING: prompts are chain-hashed per full block, a hit maps the
request's leading table entries onto the blocks already holding that
prefix (refcounted; copy-on-write when the whole prompt lands on shared
blocks), and the prefill buckets by SUFFIX length — shared-prefix
traffic skips both the duplicate storage and the shared prefill
compute. Requests sample: per-request temperature / top-k / top-p with
an independent host PRNG stream per request (``sample_seed`` replays
exactly); temperature 0 stays the bitwise greedy default, and the
program count is unchanged — ``len(prompt_buckets) + 1`` fixed
signatures, zero recompiles after warmup under any mixed traffic.

LAYER KINDS. What the engine knows of a model's layers is ONE table,
models/transformer.py `cache_pools`, read once at construction: each pool's
name and shape, what indexes its leading dimension, whether a rejected draft
rewinds from it (``speculative`` needs every pool to), whether a shared
block's entry of it copies, and the series its reads are booked under. A
kind of index other than the allocator's block ids gets a bookkeeper
(serving/kv_blocks.py), its feed beside 'gen_btab' (`_tables_feed`) and its
entry in ``stats()``; such pools are sized from the slots, and a shared
prefix is resumed over them from what the prefix cache's entries hold beside
their block (`_sides`). WINDOW LAYERS (PR 41,
``layer_types`` ``'window'``) see the last ``sliding_window`` keys and keep
K and V in a RING of blocks a slot owns while resident: a page behind the
window is written over, never handed to an allocator. STATE-SPACE LAYERS
(PR 43, ``'ssm'``) keep the recurrence's state and the convolution's tail A
ROW A SLOT, a fixed size whatever the context (a slot that sits a step out
is fed row 0, the trash row); with ``prefix_sharing`` a prefill dispatch
that ends on a block's edge leaves a copy of the row in a SNAPSHOT row
(PR 58), and a later reader of the same prefix resumes from the deepest
edge that still has one. docs/serving.md has each kind's contract.

SPECULATIVE DECODING (PR 13, ``GenerateConfig(speculative=True)``)
breaks the one-token-per-dispatch decode ceiling:
a DRAFT model (``draft_model``; default = the target config, so a
seed-built engine drafts with the target's own weights — the
100%-accept reference; an int8-converted or distilled small model is
the production draft) proposes ``spec_k`` greedy tokens per slot in
ONE dispatch (`build_lm_drafter` — the K steps are unrolled in-program,
argmax feeding the next step's embedding on-device), then the target
VERIFIES all proposals in one batched ``spec_k + 1``-wide step
(`build_lm_verify`). Accepted tokens advance both caches; the first
mismatch falls back to the target's own token — since every emitted
token IS the target's argmax given the previously emitted tokens,
greedy output is **bitwise identical** to non-speculative decode,
speculation only changes how many tokens land per dispatch (up to
``spec_k + 1``). Rejected rows roll back through the block
table: their positions sit past the accepted write head (masked to
exact zero by every later attention), and tail blocks holding no
accepted position return to the allocator — no cache bytes are copied
or cleared. The draft runs against its OWN scope (own parameters, own
paged block pool sized ``slots * max_len / block_size``) so target and
draft state never alias. Sampled requests co-resident on a speculative
engine fall the whole batch back to plain steps for those rounds
(``spec_fallback_total``) — speculation accelerates greedy traffic.

CHUNKED PREFILL (same PR): prompts longer than the widest bucket do
not reject at submit() — the prefill runs in bucket-sized chunks, each
chunk attending the cached prefix through ``kv_prefix_attention``
exactly like a shared-prefix suffix, so admission reaches
``max_len - 1`` tokens with ZERO new compiled signatures and the
continuation is bit-exact vs a single-shot prefill through a wider
bucket. Behind a decode step in flight the chunks go out ONE A PASS of
the loop (``_admit_run``): the residents' step runs between two chunks,
so a token gap holds one chunk and never a whole long prompt, and no
other admission starts before the last chunk is out.

THE LOOP IS A PIPELINE ONE STEP DEEP (PR 31): with step k dispatched and
its tokens not fetched, step k + 1 is dispatched on them as they are on
the device, and step k is fetched, delivered and booked while k + 1
computes (`GenerateEngine._loop`; ``generate_overlapped_steps_total``,
``generate_discarded_rows_total``). AN ADMISSION BLOCKS FOR NOTHING (PR
38): it dispatches the prefill and returns; the first token stays on the
device, the row joins the next step on it, and the loop picks it up
before that step's own fetch (`_pickup`;
``generate_first_token_carried_total``).

Monitor series: ``decode_tokens_total``, ``kv_slot_occupancy``,
``decode_step_seconds``, ``prefill_seconds``,
``generate_request_total{outcome=ok|error|shed|deadline|rejected|stopped}``,
``generate_queue_depth``, ``generate_step_error_total``,
``generate_warmup_total``; the block-level capacity accounting
``kv_blocks_in_use`` / ``kv_blocks_free`` gauges (these, not slot
occupancy, are the saturation signal — slots do not bound memory) and
the ``kv_block_cow_total``,
``kv_prefix_hit_total{outcome=hit|miss}`` and
``kv_prefix_tokens_saved_total`` counters. Speculative engines add
``spec_propose_total`` / ``spec_accept_total`` /
``spec_fallback_total`` counters, ``spec_draft_seconds`` /
``spec_verify_seconds`` histograms, per-request ``draft`` / ``verify``
trace stages (sub-stages of the decode wall — tools/tracereport.py
breaks them out per kind) and a ``spec_accept_rate`` field in the
request timing. Full catalog: docs/observability.md; tuning guide:
docs/serving.md.
"""
import queue as _pyqueue
import threading
import time

import numpy as np

from .. import blackbox
from .. import coldstart
from .. import goodput
from .. import monitor
from .. import trace as trace_mod
from .. import unique_name
from ..executor import Executor, Scope, scope_guard
from ..framework import Program, TPUPlace, program_guard
from ..models.transformer import (EXIT_MASS_ONE, INDEX_FEEDS, LMConfig,
                                  build_lm_decode_step,
                                  build_lm_prefill_paged, cache_pools,
                                  kv_cache_names, kv_cache_shapes)
from ..reader.bucketing import bucketize
from .kv_blocks import (BlockAllocator, PrefixCache, chain_hashes,
                        slot_bookkeeper)
from .batcher import (DeadlineExceededError, EngineStoppedError,
                      LoadShedError, Request, RequestQueue,
                      resolve_metrics_port, start_metrics_server)

__all__ = ['GenerateConfig', 'GenerateEngine', 'GenerateRequest',
           'GenerateResult']

_DONE = object()
# generate_token_gap*_total{held=...}: a delivered token gap inside which
# another request's admission completed, or none did (`_deliver`)
_HELD, _PLAIN = {'held': 'admission'}, {'held': 'none'}

def _loop_phase(name, counted=True):
    """Phase `name` of the decode loop thread (monitor.phase): its self
    time into generate_loop_seconds_total{phase=name}, and a
    'paddle_tpu:generate.<name>' span in a profiler session. The phases
    and what the device does meanwhile: docs/observability.md. Not
    `counted`, it is the span and nothing else: an admission's two phases,
    which `_pickup` books with the rest of the admission."""
    return monitor.phase('generate.' + name, 'generate_loop_seconds_total'
                         if counted else None, {'phase': name})


def _book_admission(self_s, dispatch_s):
    """An admission's phases `prefill` and `prefill.dispatch`, their
    seconds kept since (`_loop_phase(..., counted=False)`)."""
    monitor.inc('generate_loop_seconds_total', self_s, {'phase': 'prefill'})
    monitor.inc('generate_loop_seconds_total', dispatch_s,
                {'phase': 'prefill.dispatch'})


def _loop_sums():
    """The loop thread's counters as stats()['loop']: seconds by phase,
    the wall seconds of its passes, and the queue wait of the requests
    admitted. Process-wide, like the counters they are read from."""
    flat = monitor.counters()
    prefix = 'generate_loop_seconds_total{phase='
    return {
        'phase_s': {k[len(prefix):-1]: v for k, v in flat.items()
                    if k.startswith(prefix)},
        'wall_s': flat.get('generate_loop_wall_seconds_total', 0.0),
        'queue_wait_s': flat.get('generate_queue_wait_seconds_total', 0.0),
        'admitted': flat.get('generate_admit_total', 0),
    }


def _live_page_share():
    """Of the table pages the active slots' decode steps spanned, the
    share at or below the slots' positions — what the paged attention
    kernel reads. Process-wide, like `_loop_sums`."""
    flat = monitor.counters()
    table = flat.get('kv_decode_pages_table_total', 0)
    return round(flat.get('kv_decode_pages_live_total', 0) / float(table),
                 4) if table else 0.0


def _sampling_stream(sample_seed):
    """One request's private sampling PRNG: a pinned seed replays the
    stream bit-exactly; None draws a fresh unpredictable one. Shared by
    submit()-side requests and the generate_once replay path — the
    'same (seed, prompt) replays the same tokens' contract depends on
    these two staying byte-identical."""
    seed = sample_seed if sample_seed is not None \
        else np.random.SeedSequence().entropy
    return np.random.Generator(np.random.Philox(int(seed)))


class GenerateResult(list):
    """What ``GenerateRequest.result()`` returns: the generated token ids
    (it IS a list — equality/iteration/len behave like the token list)
    plus the structured completion metadata a caller routing on latency
    needs:

    - ``finish_reason``: 'eos' | 'length' | 'cache_full'
    - ``timing``: the request's latency budget — ``queue_s``,
      ``prefill_s``, ``decode_step_s`` (sum over steps), ``total_s``,
      ``tokens``, ``step_s_mean`` / ``step_s_p99`` (per-token decode
      gaps), ``admission_wait_s`` / ``admissions_waited`` (of those
      gaps, the seconds and the number that held another request's
      admission), and the ``trace_id`` joining it to the trace log
      (docs/observability.md).
    """

    def __init__(self, tokens, finish_reason=None, timing=None):
        list.__init__(self, tokens)
        self.finish_reason = finish_reason
        self.timing = timing

    @property
    def tokens(self):
        return list(self)


class GenerateConfig(object):
    """Decode-engine knobs.

    - model: an `LMConfig` (decode programs share parameter names with
      `build_lm`, so a scope trained for the LM serves directly).
    - slots: the max number of in-flight sequences.
    - max_len: the longest sequence a slot's block table can address
      (a multiple of block_size); prompt + generated tokens beyond it
      end the request with finish_reason='cache_full'.
    - prompt_buckets: ascending prompt-length ladder; one prefill program
      compiles per bucket. Default: powers of two from 16 up to max_len/2.
    - eos_id: token ending a sequence (None = length-bounded only).
    - max_new_tokens: per-request generation cap when submit() gives none.
    - queue_cap / default_deadline_s: PR 4 bounded-queue semantics.
    - seed: parameter-init seed (two engines built with equal seeds hold
      identical weights — the parity-test contract).
    - metrics_port: as ServingConfig.metrics_port (None falls back to
      PADDLE_METRICS_PORT; the endpoint rides start()/stop()).
    - block_size / num_blocks / prefix_sharing: the KV block pool.
      `num_blocks` is the PHYSICAL pool size (block 0 is the reserved
      trash block, so `num_blocks - 1` blocks are allocatable); the
      default, slots * max_len / block_size, is the HBM of `max_len`
      rows reserved for every slot. `prompt_buckets` bucket the prefill
      SUFFIX — with prefix sharing, a request's prefill cost is its
      un-cached suffix, not its prompt.
    - paged: accepted and unused — the block pool is the only cache.
      True is the only value; False raises (the contiguous cache it
      selected is gone). The benchmark's serve driver still passes it.
    - temperature / top_k / top_p: engine-wide sampling defaults applied
      when submit() passes none. 0 / 0 / 0 = bitwise greedy.
    - speculative / spec_k / draft_model: speculative decoding. A
      draft LM proposes `spec_k` greedy tokens per
      decode round in one dispatch and the target verifies all of them
      in one `spec_k + 1`-wide batched step — greedy output stays
      bitwise identical to non-speculative decode, up to spec_k + 1
      tokens land per round. `draft_model` is the draft's LMConfig
      (must share the target's vocab); None drafts with the target
      config itself (a seed-built engine then drafts with identical
      weights — the 100%-accept reference; pass a smaller config, or an
      int8-converted variant's scope via GenerateEngine(draft_scope=),
      for a cheap production draft).
    """

    def __init__(self, model=None, slots=8, max_len=256,
                 prompt_buckets=None, eos_id=None, max_new_tokens=64,
                 pad_id=0, queue_cap=256, default_deadline_s=60.0,
                 seed=0, metrics_port=None, idle_poll_s=0.02,
                 paged=True, block_size=16, num_blocks=None,
                 prefix_sharing=True, temperature=0.0, top_k=0,
                 top_p=0.0, speculative=False, spec_k=4,
                 draft_model=None):
        self.model = model or LMConfig()
        self.slots = int(slots)
        self.max_len = int(max_len)
        if self.slots < 1:
            raise ValueError("slots must be >= 1")
        if self.max_len < 2:
            raise ValueError("max_len must be >= 2")
        if not paged:
            raise ValueError(
                "paged=False: the contiguous KV cache is gone — the block "
                "pool is the only cache engine (drop the argument)")
        self.block_size = int(block_size)
        self.prefix_sharing = bool(prefix_sharing)
        if self.block_size < 1:
            raise ValueError("block_size must be >= 1")
        if self.max_len % self.block_size:
            raise ValueError(
                "max_len (%d) must be divisible by block_size (%d) — the "
                "block table is max_len/block_size entries wide"
                % (self.max_len, self.block_size))
        if num_blocks is None:
            num_blocks = self.slots * self.max_len // self.block_size
        self.num_blocks = int(num_blocks)
        if self.num_blocks < 2:
            raise ValueError("num_blocks must be >= 2 (block 0 is "
                             "the reserved trash block)")
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.speculative = bool(speculative)
        self.spec_k = int(spec_k)
        self.draft_model = draft_model
        if self.speculative:
            if self.spec_k < 1:
                raise ValueError("spec_k must be >= 1")
            if draft_model is not None and \
                    draft_model.vocab_size != self.model.vocab_size:
                raise ValueError(
                    "draft_model.vocab_size (%d) must equal the target's "
                    "(%d) — draft proposals are target token ids"
                    % (draft_model.vocab_size, self.model.vocab_size))
        if prompt_buckets is None:
            prompt_buckets, b = [], 16
            while b <= self.max_len // 2:
                prompt_buckets.append(b)
                b *= 2
            if not prompt_buckets:
                prompt_buckets = [self.max_len // 2 or 1]
        self.prompt_buckets = sorted(set(int(b) for b in prompt_buckets))
        if not self.prompt_buckets:
            raise ValueError("prompt_buckets must not be empty")
        if self.prompt_buckets[0] < 1 or \
                self.prompt_buckets[-1] > self.max_len:
            raise ValueError(
                "prompt_buckets %r must lie in [1, max_len=%d]"
                % (prompt_buckets, self.max_len))
        self.eos_id = eos_id
        self.max_new_tokens = int(max_new_tokens)
        self.pad_id = int(pad_id)
        self.queue_cap = int(queue_cap)
        self.default_deadline_s = default_deadline_s
        self.seed = int(seed)
        self.metrics_port = metrics_port
        self.idle_poll_s = float(idle_poll_s)


class GenerateRequest(Request):
    """One prompt in flight: the PR 4 future contract (`result()`,
    `fail()`, deadline) plus a per-token stream. `result()` returns a
    `GenerateResult` — the generated-token list enriched with
    ``finish_reason`` and the ``timing`` breakdown (queue/prefill/
    per-token decode); ``for tok in req.stream()`` consumes tokens as
    decode steps deliver them. `finish_reason` is
    'eos' | 'length' | 'cache_full' after a normal finish."""

    __slots__ = ('prompt', 'max_new_tokens', 'tokens', 'finish_reason',
                 'step_s', '_stream_q', 'temperature', 'top_k', 'top_p',
                 'sample_seed', '_rng', 'spec_proposed', 'spec_accepted',
                 'admission_wait_s', 'admissions_waited')

    def __init__(self, prompt, seq_len, bucket, deadline, max_new_tokens,
                 temperature=0.0, top_k=0, top_p=0.0, sample_seed=None):
        Request.__init__(self, {'prompt': prompt}, 1, seq_len, bucket,
                         deadline)
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.tokens = []
        self.finish_reason = None
        self.step_s = []        # engine-attributed per-token step times
        self._stream_q = _pyqueue.Queue()   # (bounded by max_new_tokens)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.sample_seed = sample_seed
        self._rng = None
        self.spec_proposed = 0  # draft tokens proposed for this request
        self.spec_accepted = 0  # ... that became emitted tokens
        # of its token gaps, those that held ANOTHER request's admission:
        # their seconds and their number (`GenerateEngine._deliver`)
        self.admission_wait_s = 0.0
        self.admissions_waited = 0

    def _draw_u(self):
        """Next uniform of this request's OWN sampling stream: one host
        PRNG per request, so co-resident slots sample independently and
        a (sample_seed, prompt) pair replays bit-exactly regardless of
        slot assignment or neighbors."""
        if self.temperature <= 0.0:
            return 0.0
        if self._rng is None:
            self._rng = _sampling_stream(self.sample_seed)
        return float(self._rng.random())

    # engine-side delivery ------------------------------------------------
    def _emit(self, tok):
        self.tokens.append(tok)
        self._stream_q.put(tok)

    def _finish(self, reason):
        self.finish_reason = reason
        tr = self.trace
        if tr is not None and self.timing is None:
            rec = tr.finish('ok', tokens=len(self.tokens))
            t = trace_mod.flat_timing(rec)
            t['tokens'] = len(self.tokens)
            t['finish_reason'] = reason
            if self.step_s:
                srt = sorted(self.step_s)
                t['step_s_mean'] = sum(srt) / len(srt)
                t['step_s_p99'] = srt[monitor._rank_idx(0.99, len(srt))]
            t['admission_wait_s'] = self.admission_wait_s
            t['admissions_waited'] = self.admissions_waited
            if self.spec_proposed:
                t['spec_proposed'] = self.spec_proposed
                t['spec_accepted'] = self.spec_accepted
                t['spec_accept_rate'] = round(
                    self.spec_accepted / float(self.spec_proposed), 4)
            self.timing = t
        Request.done(self, GenerateResult(self.tokens,
                                          finish_reason=reason,
                                          timing=self.timing))
        self._stream_q.put(_DONE)

    def fail(self, error):
        Request.fail(self, error)
        self._stream_q.put(_DONE)

    # consumer side -------------------------------------------------------
    def stream(self, timeout=None):
        """Yield generated tokens as they arrive; on a failed request the
        error raises AFTER the tokens already delivered. `timeout` bounds
        the wait for EACH token; with no explicit timeout the request's
        own deadline (+1s grace) bounds every wait instead — a consumer
        must never hang past its deadline, even on an engine that was
        never started (the result() contract)."""
        while True:
            t = timeout
            if t is None and self.deadline is not None:
                t = max(0.0, self.deadline - time.monotonic()) + 1.0
            try:
                item = self._stream_q.get(timeout=t)
            except _pyqueue.Empty:
                raise DeadlineExceededError(
                    "no token within %.3fs" % (t or 0.0))
            if item is _DONE:
                if self._error is not None:
                    raise self._error
                return
            yield item


class _Slot(object):
    __slots__ = ('req', 'pos', 'generated', 'last', 'last_t', 'wall0',
                 'blocks', 'table', 'dblocks', 'dtable', 'draft_stale',
                 'ahead', 'admit_seq', 'first')

    def __init__(self, req, pos, blocks, table, dblocks=None, dtable=None):
        self.req = req
        self.pos = pos          # cache position the NEXT step writes
        self.generated = 1      # the prefill made the first token
        self.last = None        # last generated token (next step's input)
        # the prefill's `_First` until the loop picks the first token up
        # (`_pickup`): `last` is on the device only, nothing is emitted
        # yet, and a step takes the row's token from the engine's buffer
        # of first tokens (`_stage_feeds`)
        self.first = None
        # pos, generated and last are as of the last step DELIVERED;
        # `ahead` counts the steps dispatched for this slot since (0 or
        # 1): the next dispatch writes pos + ahead, on a token that is
        # still on the device when ahead is 1
        self.ahead = 0
        self.last_t = time.perf_counter()   # previous token's completion
        # the engine's count of admissions at that token, its own
        # included: moved by the next token, the gap held an admission
        self.admit_seq = None
        self.wall0 = time.time() * 1e6      # decode-phase start (us)
        self.blocks = blocks    # physical block ids, table order
        self.table = table      # np [max_blocks] int64, filler 0
        self.dblocks = dblocks  # speculative: DRAFT-pool block ids
        self.dtable = dtable    # speculative: draft block table
        # plain (fallback) steps write K/V into the TARGET cache only —
        # the draft cache misses those rows until a spec round resyncs
        self.draft_stale = False


class _Flight(object):
    """A decode step between its dispatch and the fetch of its tokens."""
    __slots__ = ('out', 'active', 't0', 'overlapped', 'firsts')

    def __init__(self, out, active, t0, overlapped, firsts):
        self.out = out          # device fetches; None once a failure
        #                         took the step with it
        self.active = active    # [(slot index, _Slot)] as dispatched
        self.t0 = t0
        # dispatched while its predecessor was still unfetched
        self.overlapped = overlapped
        # the admissions since the step dispatched before this one: their
        # first tokens are picked up before this step's own fetch
        self.firsts = firsts

    def fetch(self):
        """The step's fetched vector on the host: blocks until the device
        is done with the step, and raises what an async failure left."""
        return np.asarray(self.out[0])


class _Admission(object):
    """An admission from the pop of its request to the dispatch of its
    last prefill chunk: one pass of the loop, or — a prompt wider than
    the widest bucket, behind a step in flight — a chunk a pass
    (`GenerateEngine._admit_run`)."""
    __slots__ = ('req', 'slot', 'blocks', 'table', 'hashes', 'off',
                 'published', 'sample', 't0', 'wall0', 'self_s',
                 'dispatch_s', 'fetch_s')

    def __init__(self, req, slot, blocks, table, hashes, off, sample):
        self.req = req
        self.slot = slot
        self.blocks = blocks
        self.table = table
        self.hashes = hashes
        self.off = off          # the prompt positions prefilled so far
        self.published = 0      # its full blocks offered to the prefix cache
        self.sample = sample
        self.t0 = time.perf_counter()       # the admission's start
        self.wall0 = time.time() * 1e6      # ... wall clock, us (the span)
        # what the admission took the loop: the `prefill` phase's self
        # time, the bound calls in it and the waits for its earlier
        # chunks, over all its passes
        self.self_s = self.dispatch_s = self.fetch_s = 0.0


class _First(object):
    """A prefill dispatch between its bound call and its pick-up
    (`GenerateEngine._pickup`): an admission's last, whose first token
    the pick-up fetches, or — `st` None — an earlier chunk of an
    admission still under way, whose end the pick-up waits for."""
    __slots__ = ('slot', 'st', 'out', 'adm')

    def __init__(self, slot, st, out, adm):
        self.slot = slot
        self.st = st
        self.out = out          # the prefill's device fetch
        self.adm = adm


def block_copy_fn(backend):
    """The jitted copy of one block of a cache pool onto another
    (copy-on-write; warmup compiles it). The pool is DONATED wherever the
    backend donates: without that the call holds a second pool for as
    long as it runs — in warmup() it was every serve cell's memory peak
    (2.35 GB over a 12.76 GB state in the largest, PERF.md PR 33). The
    CPU ignores donation with a warning, so none is asked of it."""
    import jax

    def _copy(cache, s, d):
        return cache.at[d].set(cache[s])
    return jax.jit(_copy, donate_argnums=() if backend == 'cpu' else (0,))


class GenerateEngine(object):
    """In-process continuous-batching decode engine. ::

        cfg = fluid.serving.GenerateConfig(
            model=LMConfig(...), slots=8, max_len=256, eos_id=1)
        engine = fluid.serving.GenerateEngine(cfg)
        engine.warmup()                      # compiles every signature
        with engine:                         # start()/stop()
            req = engine.submit(prompt_ids, max_new_tokens=32)
            for tok in req.stream():         # streams per decode step
                ...
            full = engine.submit(p2).result()

    Pass ``scope=`` to serve already-trained parameters (names match
    build_lm); otherwise the engine initializes fresh parameters from
    ``config.seed``.

    ``block_allocator=`` injects a shared pool instead of
    the engine-private default — the multi-tenant residency path: a
    `ModelFleet` sizes ONE ``BlockAllocator`` to the real HBM budget
    and hands each co-resident engine a `QuotaBlockAllocator` view, so
    per-tenant quotas are enforced while every tenant draws from the
    same physical free list. The allocator's block_size must match the
    config's; the engine's prefix cache is built over the injected
    view, keeping cache-pressure eviction tenant-local.
    """

    def __init__(self, config=None, scope=None, draft_scope=None,
                 block_allocator=None):
        self.config = config or GenerateConfig()
        self.scope = scope if scope is not None else Scope()
        self.executor = Executor(TPUPlace(0))
        c = self.config
        if block_allocator is not None:
            if block_allocator.block_size != c.block_size:
                raise ValueError(
                    "injected allocator block_size %d != config "
                    "block_size %d — the paged kernels address the "
                    "cache through the table at the allocator's "
                    "granularity" % (block_allocator.block_size,
                                     c.block_size))
            self._alloc = block_allocator
        else:
            self._alloc = BlockAllocator(c.num_blocks, c.block_size)
        self._max_blocks = c.max_len // c.block_size
        self._cow_jit = self._dcopy_jit = None
        self._move_jit = {}     # a bookkeeper's feed -> its copy program
        self._stage_jit = self._put_jit = None
        # the prefills' first tokens as they are on the device, a row a
        # slot, and a step's output to stand for "no step in flight"
        # (warmup makes both)
        self._first_buf = self._no_prev = None
        # all the engine knows of the model's layer kinds: its pools,
        # read here once -- no pass of the loop looks at the model again
        pools = self._pools = cache_pools(c.model, c.num_blocks, c.block_size,
                                          c.slots, c.prefix_sharing)
        unfit = [p for p in pools if not p.rewinds]
        if c.speculative and unfit:
            raise ValueError("speculative=True with LMConfig.layer_types=%r: "
                             "pool %r: %s" % (c.model.layer_types,
                                              unfit[0].name, unfit[0].why))
        self._free = list(range(c.slots))[::-1]
        # a wholly shared prompt's last block can be copied and resumed, in
        # every pool (a prefix is shared over each)
        self._cow_ok = all(p.copies for p in pools)

        def booked(moment):
            return tuple((p.books[moment], p.shape[1]) for p in pools
                         if moment in p.books)
        # (what is booked, of how many layers): at a decode step (series,
        # rows a slot at most) for what it reads; at a prefill dispatch the
        # series of the rows it walks and of what it resumes from
        self._step_reads, self._prefill_rows, self._resumes = \
            booked('step'), booked('prefill'), booked('resume')
        # ... and at an admission, of what it resumes from at a shared
        # prefix's edge
        self._hits = booked('hit')
        # a looped model's passes a dispatch (`_book_passes`), those run
        # so far by phase and the exit mass a pass of the decode steps
        self._passes = c.model.passes
        self._pass_runs = {'decode': 0, 'prefill': 0}
        self._exit_mass = [0.0] * self._passes
        self._pass_labels = [{'pass': str(t + 1)}
                             for t in range(self._passes)]
        self._traced_reads = {}
        if c.speculative:
            self._draft_cfg = c.draft_model or c.model
            # +1 over the all-slots-at-max_len footprint (the trash
            # block), so per-slot draft growth can never starve — the
            # draft pool needs no eviction or parking machinery
            self._draft_nb = c.slots * c.max_len // c.block_size + 1
            self._draft_alloc = BlockAllocator(self._draft_nb,
                                               c.block_size)
            self._draft_scope = draft_scope if draft_scope is not None \
                else Scope()
            # fresh draft scope + default draft config: alias the
            # TARGET's parameters (draft == target weights even for a
            # trained scope — the high-accept reference); a distinct
            # draft_model initializes from config.seed instead, and a
            # provided draft_scope serves its own (e.g. int8/distilled)
            # weights as-is
            self._draft_copies_target = draft_scope is None and \
                c.draft_model is None
        else:
            self._draft_cfg = self._draft_alloc = self._draft_scope = None
            self._draft_copies_target = False
        self._build_programs()
        # one bookkeeper a kind of index that is not the allocator's (the
        # slots' rings, the slots' rows; none for most models), its table
        # as wide as the step's feed of it
        kinds = {p.index: p for p in pools if p.index != 'block'}
        self._books = tuple(
            slot_bookkeeper(
                pool, self._step_prog.global_block().var(
                    INDEX_FEEDS[index]).shape[-1],
                c.slots, c.block_size, self._free)
            for index, pool in kinds.items())
        # the bookkeepers whose blocks a prefix's entries hold beside the
        # allocator's: a ring's blocks, or the rows' snapshots (one at
        # most: the prefix cache has one side)
        self._sides = tuple(b for b in self._books
                            if c.prefix_sharing and hasattr(b, 'resume'))
        if len(self._sides) > 1:
            raise ValueError(
                "prefix_sharing=True with LMConfig.layer_types=%r: the "
                "prefix cache's entries hold ONE side block, and this model "
                "has window layers' blocks AND state rows to keep there"
                % (c.model.layer_types,))
        self._prefix = None
        if c.prefix_sharing:
            self._prefix = PrefixCache(
                self._alloc, *(b.blocks for b in self._sides))
            for book in self._sides:
                book.cache = self._prefix
        self._init_state()
        self.queue = RequestQueue(self.config.queue_cap)
        self._slots = [None] * self.config.slots
        self._pending_admit = None   # popped but awaiting free blocks
        self._prefill_bound = {}
        self._draft_prefill_bound = {}
        self._step_bound = None
        self._handles = []      # every BoundProgram warmup made
        self._drafter_bound = None
        self._verify_bound = None
        self._thread = None
        self._started = False
        self._stop_evt = threading.Event()
        self._lock = threading.Lock()
        self._metrics_server = None
        # decode steps dispatched and not fetched yet, oldest first: two
        # at most, while the loop's pipeline is full
        self._flights = []
        # admissions whose first token is still on the device and that no
        # step in flight has taken over (`_Flight.firsts`), oldest first
        self._firsts = []
        self._fetched_t = 0.0   # when the last step's fetch came back
        # ... and the last first token picked up behind steps in flight:
        # up to there the device's time was a prefill's, no step's
        self._picked_t = 0.0
        # prefill dispatches picked up (`_Slot.admit_seq`): first tokens,
        # and the earlier chunks of a chunked admission
        self._admit_seq = 0
        # the chunked admission under way (`_admit_run`), if any: its slot
        # is neither free nor resident, and no other admission starts
        self._chunking = None
        # stats()' tallies
        self._first_carried = self._decode_steps = self._sampled_steps = 0
        self._overlapped_steps = self._discarded_rows = 0
        self._decode_tokens = self._active_peak = self._blocks_peak = 0
        self._occ_sum = self._occ_peak = 0.0
        self._spec_rounds = self._spec_proposed = self._spec_accepted = 0
        self._spec_fallbacks = self._spec_stale_rounds = 0
        self._goodput_fps = None
        # resolve + name the goodput fingerprint set NOW: a periodic
        # snapshot exporting counters before the first stats() call
        # would otherwise label them as bare fingerprints and split
        # each program's series in two
        self._goodput_fp_set()
        monitor.set_gauge('kv_slot_occupancy', 0.0)
        monitor.set_gauge('generate_queue_depth', 0.0)
        self._set_block_gauges()

    # ------------------------------------------------------------------
    # build + state
    def _build_programs(self):
        cfg, c = self.config.model, self.config
        self._step_prog, self._startup = Program(), Program()
        self._startup.random_seed = c.seed
        self._step_prog.random_seed = c.seed
        with program_guard(self._step_prog, self._startup):
            with unique_name.guard():
                self._step_vars = build_lm_decode_step(
                    cfg, c.slots, c.max_len, block_size=c.block_size,
                    num_blocks=c.num_blocks, shared=c.prefix_sharing)
        self._prefill = {}
        for b in c.prompt_buckets:
            # a bucket's prefill is a program of its own: its name says
            # which, so a device trace tells the buckets apart
            main = Program('lm_prefill_paged_b%d' % b)
            start = Program()
            main.random_seed = c.seed
            with program_guard(main, start):
                with unique_name.guard():
                    v = build_lm_prefill_paged(
                        cfg, b, c.num_blocks, c.block_size,
                        self._max_blocks, slots=c.slots,
                        shared=c.prefix_sharing)
            self._prefill[b] = (main, v)
        if c.speculative:
            from ..models.transformer import (build_lm_drafter,
                                              build_lm_verify)
            dcfg = self._draft_cfg
            self._drafter_prog = Program()
            self._draft_startup = Program()
            self._drafter_prog.random_seed = c.seed
            self._draft_startup.random_seed = c.seed
            with program_guard(self._drafter_prog, self._draft_startup):
                with unique_name.guard():
                    self._drafter_vars = build_lm_drafter(
                        dcfg, c.slots, c.max_len, c.spec_k,
                        self._draft_nb, c.block_size)
            self._verify_prog = Program()
            self._verify_prog.random_seed = c.seed
            with program_guard(self._verify_prog, Program()):
                with unique_name.guard():
                    self._verify_vars = build_lm_verify(
                        cfg, c.slots, c.spec_k + 1, c.max_len,
                        c.num_blocks, c.block_size)
            self._draft_prefill = {}
            if not self._draft_copies_target:
                # a distinct draft prefills for real; the target-copy
                # fast path block-copies instead and never runs these
                for b in c.prompt_buckets:
                    main, start = Program('lm_draft_prefill_paged_b%d'
                                          % b), Program()
                    main.random_seed = c.seed
                    with program_guard(main, start):
                        with unique_name.guard():
                            v = build_lm_prefill_paged(
                                dcfg, b, self._draft_nb, c.block_size,
                                self._max_blocks)
                    self._draft_prefill[b] = (main, v)

    @staticmethod
    def _token_fetch(v, tokens):
        """The one var a decode program's dispatch fetches: its tokens,
        or with experts the tokens and the expert loads in one vector
        (`_split_load` takes them apart)."""
        return v.get('tokens_and_load', v[tokens])

    def _split_load(self, out, n_tokens):
        """The fetched vector as tokens; the expert loads behind them
        (a model with experts) go into the moe_* counters: per layer-step
        the assignments, the experts touched and the busiest expert's
        rows — four scalar adds, no label (docs/observability.md). Behind
        a looped model's tokens lie its step's exit masses instead
        (`_book_passes`)."""
        flat = np.asarray(out).reshape(-1)
        if flat.size > n_tokens and self._passes > 1:
            self._book_passes('decode', flat[n_tokens:])
        elif flat.size > n_tokens:
            cfg = self.config.model
            load = flat[n_tokens:].reshape(cfg.n_moe_layers, -1)
            # the experts held here; a layer that holds a share only
            # counts the assignments to the others in one last column
            held = load[:, :cfg.experts_held[1]]
            monitor.inc('moe_layer_steps_total', load.shape[0])
            monitor.inc('moe_assignments_total', int(load.sum()))
            monitor.inc('moe_held_assignments_total', int(held.sum()))
            monitor.inc('moe_experts_touched_total',
                        int(np.count_nonzero(held)))
            monitor.inc('moe_max_expert_rows_total',
                        int(held.max(axis=1).sum()))
        return flat[:n_tokens]

    def _book_passes(self, phase, masses=()):
        """A looped model's dispatch (`LMConfig.passes`): the passes it
        ran into loop_passes_total{phase} -- every one, until a token may
        leave early -- and a decode step's `masses`, the exit
        distribution's mass a pass over its live rows as the program
        returns them (`EXIT_MASS_ONE`), into loop_exit_mass_total{pass}:
        the mean pass at which a threshold would let go, read off a
        running server (docs/observability.md)."""
        monitor.inc('loop_passes_total', self._passes,
                    labels={'phase': phase})
        self._pass_runs[phase] += self._passes
        for t, mass in enumerate(masses):
            mass = float(mass) / EXIT_MASS_ONE
            monitor.inc('loop_exit_mass_total', mass,
                        labels=self._pass_labels[t])
            self._exit_mass[t] += mass

    def _init_state(self):
        cfg, c = self.config.model, self.config
        with scope_guard(self.scope):
            if not self.scope.has('tok_emb.w'):
                # fresh engine: init params from config.seed; a provided
                # scope with trained weights skips this entirely
                self.executor.run(self._startup, scope=self.scope)
        if c.speculative and not self._draft_scope.has('tok_emb.w'):
            if self._draft_copies_target:
                # alias the target's parameter arrays (jax arrays are
                # immutable — zero-copy); the caches are NOT copied,
                # _ensure_cache gives the draft scope its own pool
                for name in self.scope.names():
                    if name not in kv_cache_names(cfg):
                        self._draft_scope.set(name, self.scope.get(name))
            else:
                with scope_guard(self._draft_scope):
                    self.executor.run(self._draft_startup,
                                      scope=self._draft_scope)
        self._ensure_cache()

    def _ensure_cache(self):
        """Make the scope's pools (`cache_pools`) match THIS engine's
        geometry. A provided scope may carry another engine's cache under
        the same names with a different pool shape; the cache holds no
        trained state, so re-zeroing is always safe, while reusing a
        mismatched buffer would feed the compiled programs garbage shapes.
        Re-checked at warmup()/start()/generate_once() so engines sharing
        one trained scope SEQUENTIALLY each reclaim it (concurrent use of
        one scope by two live engines stays unsupported)."""
        import jax.numpy as jnp
        c = self.config
        pools = [(self.scope, {p.name: p.shape for p in self._pools})]
        if c.speculative:
            pools.append((self._draft_scope, kv_cache_shapes(
                self._draft_cfg, self._draft_nb, c.block_size)))
        for scope, shapes in pools:
            for name, shape in shapes.items():
                have = scope.get(name)
                if have is None or tuple(have.shape) != shape:
                    scope.set(name, jnp.zeros(shape, 'float32'))

    # ------------------------------------------------------------------
    # feed + block helpers
    @staticmethod
    def _sample_feed(n, temp=0.0, topk=0, topp=0.0, u=0.0):
        return {'gen_temp': np.full((n, 1), temp, 'float32'),
                'gen_topk': np.full((n, 1), topk, 'int64'),
                'gen_topp': np.full((n, 1), topp, 'float32'),
                'gen_u': np.full((n, 1), u, 'float32')}

    def _cow_copy(self, src, dst):
        """Device-side block copy for copy-on-write: duplicate physical
        block `src` into `dst` in BOTH caches. One jitted
        dynamic-slice/update pair, compiled once at warmup (src/dst are
        traced scalars), donation aliases the pool in place."""
        import jax
        if self._cow_jit is None:
            self._cow_jit = block_copy_fn(jax.default_backend())
        s = np.asarray(src, 'int32')
        d = np.asarray(dst, 'int32')
        for pool in self._pools:
            if pool.index != 'block':
                continue    # not the allocator's: no block of it is shared
            self.scope.set(pool.name, self._cow_jit(
                self.executor._state_value(self.scope, pool.name,
                                           self._step_prog, cache=False),
                s, d))

    def _draft_cache_sync(self, dblocks, blocks):
        """Draft == target fast path: the draft prefill would recompute
        EXACTLY the K/V rows the target prefill just wrote (same
        config, aliased weights, same inputs), so copy the target's
        prompt blocks across pools device-side instead — one jitted
        scatter replaces a whole prefill forward. Fixed-width id
        vectors (trash-padded) keep it one compiled signature."""
        import jax
        if self._dcopy_jit is None:
            def _copy(dst, src, d_ids, s_ids):
                return dst.at[d_ids].set(src[s_ids])
            self._dcopy_jit = jax.jit(_copy)
        d_ids = np.zeros((self._max_blocks,), 'int32')
        s_ids = np.zeros((self._max_blocks,), 'int32')
        d_ids[:len(dblocks)] = dblocks
        s_ids[:len(blocks)] = blocks
        for name in (pool.name for pool in self._pools):
            dst = self.executor._state_value(
                self._draft_scope, name, self._drafter_prog, cache=False)
            src = self.executor._state_value(
                self.scope, name, self._step_prog, cache=False)
            self._draft_scope.set(name,
                                  self._dcopy_jit(dst, src, d_ids, s_ids))

    def _stage_feeds(self, prev, src, toks, feed):
        """The step's integer feeds (`feed`: positions and tables) and
        its input tokens as they are on the device, from ONE host array:
        a host array costs a transfer of its own whatever its size
        (~0.12 ms on a v5e's host), so `feed`'s columns, `toks` and `src`
        go up side by side and one tiny jitted program, compiled at
        warmup, splits them again. The same program is the select of
        'gen_tokens' where a row's token is on the device: row i takes
        the token step `prev` (its device fetch, the tokens leading it;
        None with no step in flight) made for it where `src[i]` is 1, its
        prefill's first token (`_put_first`) where 2, the host's
        `toks[i]` elsewhere — an empty row, or one whose last token was
        delivered. Neither token crosses to the host and back."""
        import jax
        if self._stage_jit is None:
            import jax.numpy as jnp
            S = self.config.slots
            # a feed's name and columns, in the array's order
            layout = [(n, feed[n].shape[1]) for n in sorted(feed)]

            def stage_step_feeds(prev, first, ints):
                out, at = {}, 0
                for name, width in layout:
                    out[name] = ints[:, at:at + width]
                    at += width
                toks, src = ints[:, at:at + 1], ints[:, at + 1:at + 2]
                mine = prev.reshape(-1)[:S].reshape(S, 1)
                out['gen_tokens'] = jnp.where(
                    src == 1, mine.astype(toks.dtype),
                    jnp.where(src == 2, first.astype(toks.dtype), toks))
                return out
            self._stage_jit = jax.jit(stage_step_feeds)
        ints = np.concatenate(
            [feed[n] for n in sorted(feed)] + [toks, src], axis=1)
        return self._stage_jit(self._no_prev if prev is None else prev,
                               self._first_buf, ints)

    def _put_first(self, slot, out):
        """A prefill's first token (its device fetch, the token leading
        it) into row `slot` of the buffer the next step's select reads:
        one jitted update, compiled at warmup, behind the prefill on the
        device."""
        import jax
        if self._put_jit is None:
            # its module's name is what tools/gapreport.py counts
            def first_token_put(buf, out, slot):
                return buf.at[slot, 0].set(
                    out.reshape(-1)[0].astype(buf.dtype))
            self._put_jit = jax.jit(first_token_put)
        self._first_buf = self._put_jit(self._first_buf, out,
                                        np.asarray(slot, 'int32'))

    def _set_block_gauges(self):
        used = self._alloc.in_use()
        self._blocks_peak = max(self._blocks_peak, used)
        monitor.set_gauge('kv_blocks_in_use', float(used))
        monitor.set_gauge('kv_blocks_free', float(self._alloc.available()))

    def _alloc_blocks(self, n):
        """n blocks, evicting idle prefix-cache entries under pressure;
        None when the pool genuinely cannot satisfy the request."""
        ids = self._alloc.alloc(n)
        if ids is None and self._prefix is not None:
            # a large cache gives up a 64th of its entries beyond the need:
            # finding the least recently used sorts them all, and a full
            # pool would have every new block of every slot pay for that
            self._prefix.evict_for(n + len(self._prefix) // 64)
            ids = self._alloc.alloc(n)
        if ids is not None:
            self._set_block_gauges()
        return ids

    def _deref_blocks(self, blocks):
        self._alloc.deref_many(blocks)
        self._set_block_gauges()

    def _release_blocks(self, st):
        self._deref_blocks(st.blocks)
        st.blocks = []
        if st.dblocks:
            self._draft_alloc.deref_many(st.dblocks)
            st.dblocks = []

    def _slot_table(self, blocks):
        table = np.zeros((self._max_blocks,), 'int64')
        table[:len(blocks)] = blocks
        return table

    def _tables_feed(self, btab, slots=()):
        """A program's table feeds: 'gen_btab', and beside it what each
        bookkeeper holds for `slots` ((row, slot) pairs: a slot's ring, its
        row; the other rows all zero, the trash block or row, as an idle's)."""
        feed = {'gen_btab': btab}
        for book in self._books:
            slots = tuple(slots)
            table = np.zeros((len(btab), book.width), 'int64')
            for row, slot in slots:
                table[row] = book.table(slot)
            feed[book.feed] = table
        return feed

    def _slot_books(self, slot, length=None, start=None):
        """Book with every bookkeeper that `slot`'s tenant is about to
        write positions `start` .. `length` - 1 (a step: the last alone), or
        (None) that it is gone; what a ring hands on meanwhile goes into
        the bookkeeper's series, and a shared block that the dispatch at
        hand still reads out of a column it writes is copied first."""
        for book in self._books:
            n = book.release(slot) if length is None \
                else book.advance(slot, length, start)
            if n:
                monitor.inc(book.series, n)
            self._copy_moved(book)

    def _copy_moved(self, book):
        """Make the copies that `book` has pending (`moved`), booked under
        its series of copied rows where it has one."""
        moved = book.moved()
        if moved:
            self._move_blocks(book, moved)
            if book.copied:
                monitor.inc(book.copied,
                            len(moved) * self.config.block_size)

    def _move_blocks(self, book, moved):
        """Copy blocks or rows `moved` ((from, to) ids) in every pool that
        `book` keeps, on the device, ahead of the dispatch that reads them:
        one jitted gather and scatter a pool, the ids padded with the trash
        block to `book.batch` (a ring: the widest bucket's blocks and one)
        -- one signature, compiled at warmup; a snapshot row is ONE row, a
        DMA under the named scope `paddle_tpu:state_snapshot`
        (ops/ssm_ops.py `snapshot_copy`). Phase `prefill.move` inside
        `prefill`, as the dispatch is."""
        import jax
        width = book.batch or (
            self.config.prompt_buckets[-1] // self.config.block_size + 1)
        if book.feed not in self._move_jit:
            def _move(cache, src, dst):
                if book.scope is None:
                    return cache.at[dst].set(cache[src])
                from ..ops import ssm_ops
                return ssm_ops.snapshot_copy(cache, src, dst, book.scope)
            self._move_jit[book.feed] = jax.jit(
                _move, donate_argnums=()
                if jax.default_backend() == 'cpu' else (0,))
        move = self._move_jit[book.feed]
        with _loop_phase('prefill.move', counted=False):
            for at in range(0, len(moved), width):
                ids = np.zeros((2, width), 'int32')
                part = moved[at:at + width]
                ids[:, :len(part)] = np.asarray(part, 'int32').T
                for pool in self._pools:
                    if INDEX_FEEDS[pool.index] != book.feed:
                        continue
                    self.scope.set(pool.name, move(
                        self.executor._state_value(
                            self.scope, pool.name, self._step_prog,
                            cache=False), ids[0], ids[1]))

    # ------------------------------------------------------------------
    # warmup
    def warmup(self):
        """Bind + compile every signature the engine will ever dispatch:
        one prefill per prompt bucket and the decode step. Returns
        {'buckets', 'compiles', 'reused', 'seconds'}; `compiles` is the
        compile_cache_miss delta — 0 when a structurally identical engine
        already warmed the process-wide fingerprint cache. Signatures
        register in the warmup farm (paddle_tpu.warmfarm), so `reused`
        reports how many of this engine's cells were already compiled by
        an earlier process-sharing consumer (bind() still executes each
        program once — it must prime THIS engine's KV-cache state — but
        a reused cell binds at cache-hit speed, compile_seconds ≈ 0)."""
        if self._started:
            # bind() EXECUTES each program once: re-warming a live engine
            # would zero cache rows of resident slots mid-generation
            raise RuntimeError(
                "warmup() executes the decode programs against the live "
                "KV cache and must not race the started engine loop — "
                "warm up before start() (start() warms up automatically)")
        self._ensure_cache()
        del self._handles[:]
        from ..warmfarm import farm
        t0 = time.perf_counter()
        before = monitor.counters()
        S = self.config.slots
        reused = 0
        with coldstart.stage('first_run', 'generate.warmup'):
            # the step first: it runs on every token, so where the
            # backend lets a bound entry choose how its weights lie
            # (BoundProgram), the step chooses and the prefills, bound
            # on the same scope after it, take the weights as they lie
            toks = np.zeros((S, 1), 'int64')
            ints = {'gen_pos': np.zeros((S, 1), 'int64')}
            ints.update(self._tables_feed(
                np.zeros((S, self._max_blocks), 'int64')))
            feed = dict(ints, gen_tokens=toks, **self._sample_feed(S))
            fetch = [self._token_fetch(self._step_vars, 'next_tokens')]
            key, already = farm.track(
                self.executor, self._step_prog, feed, fetch_list=fetch,
                scope=self.scope)
            self._step_bound = self._bind(
                self._step_prog, feed, fetch_list=fetch, scope=self.scope)
            if already:
                reused += 1
            else:
                farm.commit(key)
            for b, (prog, v) in sorted(self._prefill.items()):
                # an all-zero block table points every write at the
                # reserved trash block — warmup never touches a row a
                # live request could own
                pfeed = {'gen_prompt': np.zeros((1, b), 'int64'),
                         'gen_len': np.ones((1, 1), 'int64'),
                         'gen_pos': np.zeros((1, b), 'int64')}
                pfeed.update(self._tables_feed(
                    np.zeros((1, self._max_blocks), 'int64')))
                pfeed.update(self._sample_feed(1))
                fetch = [self._token_fetch(v, 'first_token')]
                key, already = farm.track(self.executor, prog, pfeed,
                                          fetch_list=fetch,
                                          scope=self.scope)
                self._prefill_bound[b] = self._bind(
                    prog, pfeed, fetch_list=fetch, scope=self.scope)
                if already:
                    reused += 1
                else:
                    farm.commit(key)
            # the loop feeds the step from the device: its predecessor's
            # tokens as they are there and the host's feeds split there
            # (`_stage_feeds`; int32 on the device, with x64 off, where
            # the numpy feed is int64): compile that program and run the
            # step on its output now. It is the executable just bound and
            # no second one (tests/test_decode_pipeline.py counts jax's
            # own compiles); all-zero tables keep the writes in the trash
            # block.
            # ... and the select's other source, the buffer the prefills'
            # first tokens are written into (`_put_first`: the widest
            # bucket's output here, every bucket's has its shape).
            import jax.numpy as jnp
            out = self._step_bound(feed, return_numpy=False)
            self._no_prev = out[0]
            # zeros as the step's output is held (committed to its device
            # or not): `_put_first`'s input here is what its own output is
            # later, one signature
            self._first_buf = jnp.zeros_like(out[0]).reshape(-1)[:S] \
                .reshape(S, 1)
            self._put_first(0, self._prefill_bound[
                self.config.prompt_buckets[-1]](pfeed,
                                                return_numpy=False)[0])
            staged = self._stage_feeds(
                out[0], np.zeros((S, 1), 'int8'), toks, ints)
            self._step_bound(dict(staged, **self._sample_feed(S)),
                             return_numpy=False)
            if self.config.speculative:
                reused += self._warm_spec(farm)
            # compile the copy-on-write block copy now (0 -> 0 is a
            # trash-block no-op) so steady traffic stays at zero
            # compiles even when the first COW lands mid-stream
            self._cow_copy(0, 0)
            for book in self._sides:    # ... and a shared ring block's
                self._move_blocks(book, [(0, 0)])
            if self.config.speculative and self._draft_copies_target:
                # ... and the draft-pool prompt-block copy (same
                # trash-block no-op) for the draft==target fast path
                self._draft_cache_sync([0], [0])
        delta = monitor.counter_delta(before)
        compiles = sum(v for k, v in delta.items()
                       if k.startswith('compile_cache_miss'))
        monitor.inc('generate_warmup_total')
        return {'buckets': len(self._prefill_bound),
                'compiles': int(compiles), 'reused': int(reused),
                'seconds': round(time.perf_counter() - t0, 3)}

    def _bind(self, program, feed, fetch_list, scope):
        """`Executor.bind`, the handle kept for stats()['bound_restages']
        and ['bound_relayouts']."""
        bound = self.executor.bind(program, feed, fetch_list=fetch_list,
                                   scope=scope)
        self._handles.append(bound)
        return bound

    def _warm_spec(self, farm):
        """Bind + compile the speculative signature set: one DRAFT
        prefill per prompt bucket (against the draft scope), the
        drafter (spec_k unrolled greedy steps) and the target's verify
        step. All-zero block tables and vmasks route every warmup write
        to the trash block of the respective pool. Returns how many
        cells the warmup farm had already compiled."""
        c = self.config
        S, K = c.slots, c.spec_k
        reused = 0
        # draft == target: admissions block-copy the target's prompt
        # rows across pools (_draft_cache_sync), so the draft prefill
        # programs are never dispatched — don't pay their compiles
        prefills = {} if self._draft_copies_target else \
            self._draft_prefill
        # the drafter before the draft's prefills, as the step before the
        # target's (warmup): who runs every round chooses the layouts
        feed = {'gen_tokens': np.zeros((S, 1), 'int64'),
                'gen_pos': np.zeros((S, 1), 'int64'),
                'gen_btab': np.zeros((S, self._max_blocks), 'int64'),
                'gen_vmask': np.zeros((S, K + 1), 'int64')}
        fetches = [self._drafter_vars['draft_tokens']]
        key, already = farm.track(self.executor, self._drafter_prog,
                                  feed, fetch_list=fetches,
                                  scope=self._draft_scope)
        self._drafter_bound = self._bind(
            self._drafter_prog, feed, fetch_list=fetches,
            scope=self._draft_scope)
        if already:
            reused += 1
        else:
            farm.commit(key)
        for b, (prog, v) in sorted(prefills.items()):
            feed = {'gen_prompt': np.zeros((1, b), 'int64'),
                    'gen_len': np.ones((1, 1), 'int64'),
                    'gen_pos': np.zeros((1, b), 'int64'),
                    'gen_btab': np.zeros((1, self._max_blocks), 'int64')}
            feed.update(self._sample_feed(1))
            key, already = farm.track(self.executor, prog, feed,
                                      fetch_list=[v['first_token']],
                                      scope=self._draft_scope)
            self._draft_prefill_bound[b] = self._bind(
                prog, feed, fetch_list=[v['first_token']],
                scope=self._draft_scope)
            if already:
                reused += 1
            else:
                farm.commit(key)
        feed = {'gen_tokens': np.zeros((S, K + 1), 'int64'),
                'gen_pos': np.zeros((S, K + 1), 'int64'),
                'gen_btab': np.zeros((S, self._max_blocks), 'int64'),
                'gen_vmask': np.zeros((S, K + 1), 'int64')}
        key, already = farm.track(
            self.executor, self._verify_prog, feed,
            fetch_list=[self._verify_vars['verify_tokens']],
            scope=self.scope)
        self._verify_bound = self._bind(
            self._verify_prog, feed,
            fetch_list=[self._verify_vars['verify_tokens']],
            scope=self.scope)
        if already:
            reused += 1
        else:
            farm.commit(key)
        return reused

    # ------------------------------------------------------------------
    # lifecycle
    def start(self):
        with self._lock:
            if self._started:
                return self
            if self.queue.closed:
                raise EngineStoppedError(
                    "a stopped GenerateEngine cannot restart — build a "
                    "fresh engine (the queue already failed its callers)")
            if self._step_bound is None:
                self.warmup()
            else:
                self._ensure_cache()
            self._started = True
            if self._metrics_server is None:
                self._metrics_server = start_metrics_server(
                    self._resolve_metrics_port(), 'GenerateEngine')
            self._stop_evt.clear()
            self._thread = threading.Thread(target=self._loop,
                                            name='paddle-generate',
                                            daemon=True)
            self._thread.start()
        return self

    def stop(self, timeout_s=10.0):
        """Close the queue (queued requests fail with EngineStoppedError),
        fail resident generations, join the decode loop."""
        with self._lock:
            self._started = False
        self._stop_evt.set()
        drained = self.queue.close()
        if drained:
            monitor.inc('generate_request_total', drained,
                        labels={'outcome': 'stopped'})
        if self._thread is not None:
            self._thread.join(timeout_s)
            self._thread = None
        if self._prefix is not None:
            # a stopped engine cannot serve another hit; release the
            # cache's block references so accounting reads empty
            self._prefix.drop_all()
            self._set_block_gauges()
        if self._metrics_server is not None:
            self._metrics_server.close()
            self._metrics_server = None

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    def _resolve_metrics_port(self):
        return resolve_metrics_port(self.config.metrics_port)

    @property
    def metrics_port(self):
        return self._metrics_server.port if self._metrics_server else None

    # ------------------------------------------------------------------
    # request path
    def submit(self, prompt, max_new_tokens=None, deadline_s=None,
               temperature=None, top_k=None, top_p=None,
               sample_seed=None):
        """Enqueue one prompt (1-D int token ids); returns the
        `GenerateRequest` stream/future. Raises ValueError synchronously
        for prompts the cache cannot hold and `LoadShedError` when the
        bounded queue is full.

        temperature/top_k/top_p default to the engine-wide
        `GenerateConfig` values; temperature <= 0 is bitwise greedy.
        `sample_seed` pins the request's private sampling stream — the
        same (seed, prompt) replays the same tokens whatever else is
        co-resident; None draws a fresh unpredictable stream."""
        prompt = np.asarray(prompt, dtype='int64').reshape(-1)
        buckets = self.config.prompt_buckets
        # chunked prefill lifts admission past the bucket ladder: an
        # over-wide prompt prefills in bucket-sized chunks, each
        # attending the cached prefix — only the cache length bounds it
        # (one row must remain for the first decode write)
        limit = self.config.max_len - 1
        if prompt.size < 1 or prompt.size > limit:
            monitor.inc('generate_request_total',
                        labels={'outcome': 'rejected'})
            raise ValueError(
                "prompt length %d outside [1, %d] (max_len - 1: the "
                "chunked-prefill admission bound)" % (prompt.size, limit))
        if max_new_tokens is None:
            max_new_tokens = self.config.max_new_tokens
        if int(max_new_tokens) < 1:
            monitor.inc('generate_request_total',
                        labels={'outcome': 'rejected'})
            raise ValueError("max_new_tokens must be >= 1")
        c = self.config
        temperature = c.temperature if temperature is None \
            else float(temperature)
        top_k = c.top_k if top_k is None else int(top_k)
        top_p = c.top_p if top_p is None else float(top_p)
        if top_p < 0.0 or top_p > 1.0:
            monitor.inc('generate_request_total',
                        labels={'outcome': 'rejected'})
            raise ValueError("top_p must lie in [0, 1] — 0 (or 1) "
                             "disables nucleus sampling")
        if deadline_s is None:
            deadline_s = self.config.default_deadline_s
        deadline = (time.monotonic() + deadline_s
                    if deadline_s is not None else None)
        req = GenerateRequest(prompt, prompt.size,
                              bucketize(min(prompt.size, buckets[-1]),
                                        buckets), deadline,
                              int(max_new_tokens),
                              temperature=temperature, top_k=top_k,
                              top_p=top_p, sample_seed=sample_seed)
        req.trace = trace_mod.start('generate')
        try:
            self.queue.put(req)
        except (LoadShedError, EngineStoppedError) as e:
            # finishes the trace with the right outcome (keep-errors)
            monitor.inc('generate_request_total', labels={
                'outcome': 'shed' if isinstance(e, LoadShedError)
                else 'stopped'})
            req.fail(e)
            raise
        monitor.set_gauge('generate_queue_depth', self.queue.depth())
        return req

    def generate(self, prompt, max_new_tokens=None, deadline_s=None,
                 timeout=None, temperature=None, top_k=None, top_p=None,
                 sample_seed=None):
        """Blocking convenience: submit + result (the generated tokens)."""
        return self.submit(prompt, max_new_tokens=max_new_tokens,
                           deadline_s=deadline_s, temperature=temperature,
                           top_k=top_k, top_p=top_p,
                           sample_seed=sample_seed).result(timeout)

    def generate_once(self, prompt, max_new_tokens=None, temperature=0.0,
                      top_k=0, top_p=0.0, sample_seed=None):
        """Synchronous single-prompt decode on slot 0, driving the SAME
        compiled prefill/step programs step by step — the sequential
        reference the parity tests compare the continuous batcher
        against, and a zero-thread debug path. Greedy by default;
        sampling args mirror submit() (a pinned `sample_seed` replays
        the exact submit() sampling stream). Only valid while the engine
        is NOT started (it shares the loop's cache slots). The
        reference's blocks come from the live pool (bypassing the prefix
        cache) and every one is returned before returning."""
        if self._started:
            raise RuntimeError(
                "generate_once drives the decode programs inline and "
                "must not race the started engine loop — use submit()")
        if self._step_bound is None:
            self.warmup()
        else:
            self._ensure_cache()
        prompt = np.asarray(prompt, dtype='int64').reshape(-1)
        if max_new_tokens is None:
            max_new_tokens = self.config.max_new_tokens
        c = self.config
        temperature = float(temperature)
        rng = [None]

        def draw_u():
            if temperature <= 0.0:
                return 0.0
            if rng[0] is None:
                rng[0] = _sampling_stream(sample_seed)
            return float(rng[0].random())

        sample = (temperature, int(top_k), float(top_p))
        blocks = self._alloc_blocks(-(-prompt.size // c.block_size))
        if blocks is None:
            raise RuntimeError(
                "paged KV pool cannot hold a %d-token prompt right "
                "now (%d blocks free of %d)"
                % (prompt.size, self._alloc.available(),
                   self._alloc.capacity))
        table = self._slot_table(blocks)
        try:
            first = int(self._split_load(self._run_prefill(
                prompt, table, sample + (draw_u(),), slot=0), 1)[0])
            tokens, last, pos = [first], first, prompt.size
            while (len(tokens) < max_new_tokens and pos < c.max_len and
                   (c.eos_id is None or last != c.eos_id)):
                if pos // c.block_size >= len(blocks):
                    grown = self._alloc_blocks(1)
                    if grown is None:     # pool dry: cache_full semantics
                        break
                    table[len(blocks)] = grown[0]
                    blocks.append(grown[0])
                S = c.slots
                toks = np.zeros((S, 1), 'int64')
                posf = np.zeros((S, 1), 'int64')
                toks[0], posf[0] = last, pos
                btab = np.zeros((S, self._max_blocks), 'int64')
                btab[0] = table
                feed = {'gen_tokens': toks, 'gen_pos': posf}
                # the books first: a step that opens a block reads its
                # slot's table with the block in it
                self._slot_books(0, pos + 1)
                feed.update(self._tables_feed(btab, [(0, 0)]))
                sf = self._sample_feed(S)
                sf['gen_temp'][0], sf['gen_topk'][0] = sample[0], sample[1]
                sf['gen_topp'][0], sf['gen_u'][0] = sample[2], draw_u()
                feed.update(sf)
                out = self._step_bound(feed)
                last = int(self._split_load(out[0], S)[0])
                tokens.append(last)
                pos += 1
            return tokens
        finally:
            self._deref_blocks(blocks)
            self._slot_books(0)

    # ------------------------------------------------------------------
    # decode loop
    def _loop(self):
        """The decode loop: a pipeline one step deep. With step k
        dispatched and not yet fetched, a pass dispatches step k + 1 — a
        carried row's input token taken from step k's output ON THE
        DEVICE, its position pos + 1, its table grown on the host — and
        only then fetches step k, delivers and books it, while k + 1
        computes: the token's round trip to the host, `wait`, `deliver`,
        `feed` and `dispatch` all run behind a busy device. Nothing in
        flight (the start, after an idle) or a speculative round due:
        the pass is the serial one, admit, then dispatch. Only an `eos`
        finish depends on a token's value: such a row is in step k + 1
        already and `_deliver` drops its result there; every other
        finish is foreseen and the row left out (`_grow_blocks`).

        A pass admits last, in one fixed order: fetch and deliver step
        k, the consumers' turn (`yield`), then `_admit`. Behind a step
        in flight an admission is its prefill's bound call and nothing
        more (`_admit_one`): the prefill queues behind step k + 1, the
        row joins step k + 2 — the next pass's dispatch, a moment later
        — on its first token as the prefill leaves it on the device, and
        the loop picks that token up before step k + 2's own fetch
        (`_pickup`), with the prefill long done or nearly so. A prompt
        wider than the widest bucket is one CHUNK a pass (`_admit_run`):
        the device runs step, chunk, step, chunk, and the step behind a
        chunk waits for it before its own fetch. Wherever
        the admission sat in the pass the device would run the same
        programs in the same order; placed last it catches the client
        whose request the delivery just ended, in the same pass (placed
        between the dispatch and the fetch: chat's `ttft_p95_ms` + 27 %,
        OLMoE's first-token p95 + 35 %, both cells' p95 token gap level.
        PERF.md, PR 38).

        Every stretch of a pass is a phase (_loop_phase): its self time
        goes to generate_loop_seconds_total{phase=...} and, in a profiler
        session, it is a 'paddle_tpu:generate.<phase>' span on the device
        trace's clock, so a device idle gap has a name."""
        poll = self.config.idle_poll_s
        done = None     # the last fetched step, its outputs held (below)
        t_pass = time.perf_counter()
        while not self._stop_evt.is_set():
            # the wall time of the pass just ended, beside the phases'
            # self times: what no phase covers is then itself a number
            now = time.perf_counter()
            monitor.inc('generate_loop_wall_seconds_total', now - t_pass)
            t_pass = now
            flight = self._flights[0] if self._flights else None
            nxt = None
            if flight is not None and not self._spec_ready():
                nxt = self._fallback_dispatch(prev=flight)
                # the step BEFORE `flight` gives up its fetched outputs
                # here, behind the device's work. Freed between two
                # steps instead (0.3-0.5 ms, and the client threads take
                # the GIL there) a finished client's next request is
                # admitted a pass later: another schedule (PERF.md, PR
                # 25)
                done = None
            if flight is not None:
                self._step_complete(flight, nxt)
                done = flight   # noqa: F841 — held, above
            with _loop_phase('admit'):
                # an evicted slot may be in the snapshot of a step in
                # flight, and have a new tenant before that step lands:
                # `_deliver` books a row only for the tenant it was
                # dispatched for
                self._evict_expired()
                if nxt is None:
                    self._admit()
            if nxt is not None:
                # queue pops and the prefills' bound calls, behind the
                # step just dispatched: a prefill queues on the device
                # and the loop goes on
                with _loop_phase('admit_overlapped'):
                    self._admit()
            if flight is not None:
                continue
            if not any(s is not None for s in self._slots):
                with _loop_phase('idle'):
                    if self._pending_admit is not None:
                        # parked for blocks with nothing resident:
                        # _admit() retries it at the top of every loop
                        # pass (it can only be reachable transiently —
                        # with no residents the prefix cache is fully
                        # evictable)
                        time.sleep(poll)
                        continue
                    # idle: block briefly for new work instead of spinning
                    batch, expired = self.queue.take_batch(1, 0.0,
                                                           poll_s=poll)
                    self._fail_expired(expired)
                    if batch:
                        with _loop_phase('admit'):
                            self._admit_one(batch[0])
                    monitor.set_gauge('generate_queue_depth',
                                      self.queue.depth())
                continue
            if self._spec_ready():
                with _loop_phase('spec_round'):
                    self._spec_round()
                continue
            self._fallback_dispatch()
        while self._flights:
            # stopping: the steps in flight land first, so nothing on
            # the device still runs on this engine's state
            self._step_complete(self._flights[0])
        firsts, self._firsts = self._firsts, []
        self._pickup(firsts)    # ... and the prefills no step took over
        monitor.inc('generate_loop_wall_seconds_total',
                    time.perf_counter() - t_pass)      # the last pass
        # shutdown: a resident generation must not leave its caller
        # blocked forever
        for i, st in enumerate(self._slots):
            if st is not None:
                self._release(i)
                monitor.inc('generate_request_total',
                            labels={'outcome': 'stopped'})
                st.req.fail(EngineStoppedError(
                    "engine stopped after %d generated tokens"
                    % st.generated))
        if self._pending_admit is not None:
            req, self._pending_admit = self._pending_admit, None
            monitor.inc('generate_request_total',
                        labels={'outcome': 'stopped'})
            req.fail(EngineStoppedError(
                "engine stopped while the request waited for KV blocks"))
        self._drop_chunking(EngineStoppedError(
            "engine stopped between two chunks of the request's prefill"),
            'stopped')
        self._set_occupancy()

    def _admit(self):
        if self._chunking is not None:
            # its next chunk is this pass's admission, and no other
            # starts before its last
            adm, self._chunking = self._chunking, None
            self._admit_run(adm)
            return
        while self._free and self._chunking is None \
                and not self._stop_evt.is_set():
            req = self._pending_admit
            self._pending_admit = None
            if req is None:
                batch, expired = self.queue.take_batch(1, 0.0, poll_s=0.0)
                self._fail_expired(expired)
                if not batch:
                    return
                req = batch[0]
            if not self._admit_one(req):
                return      # parked for blocks: retry next token boundary
            monitor.set_gauge('generate_queue_depth', self.queue.depth())

    def _paged_plan(self, req):
        """Block plan for one admission: (blocks, ctx_len, hashes, sides).
        `blocks` covers the whole prompt in logical order — prefix-cache
        hits mapped to their existing physical blocks (referenced),
        fresh blocks for the rest, and a copy-on-write duplicate of the
        final shared block when the ENTIRE prompt landed on shared
        blocks (its last position must be recomputed, a divergent
        write; a model with a pool that does not copy recomputes that
        whole block into a fresh one instead). `sides`: the blocks that a
        bookkeeper's pool still holds of the rows before ``ctx_len``,
        referenced (`_sides`; none for most models). Returns None when the
        pool cannot satisfy the request now (nothing referenced or
        allocated)."""
        c = self.config
        bs = c.block_size
        L = req.prompt.size
        total = -(-L // bs)
        shared, hashes, sides = [], [], []
        if self._prefix is not None:
            hashes = chain_hashes(req.prompt, bs)
            shared = self._prefix.match(hashes)
        whole = bool(shared) and len(shared) * bs >= L
        n_keep = len(shared) - (1 if whole else 0)
        for book in self._sides:
            # a pool a slot has a ring in resumes where the cache still
            # holds the rows a query there reads of it: at the chain's end,
            # else at the deepest depth before it that has them (a shorter
            # hit still saves its share of the prefill), else not at all
            n_keep, sides = self._prefix.side_run(hashes, n_keep, book.reach)
            shared, whole = shared[:n_keep], False
        # a wholly shared prompt: copy its last block and recompute the
        # final row. A model with convolution layers RECOMPUTES that block
        # into a fresh one instead: a copied entry of the tails' pool
        # holds g of the block's last rows, and recomputing the last
        # position alone would need the rows before them
        cow = whole and self._cow_ok
        ctx_len = min(n_keep * bs + (bs if cow else 0), L - 1)
        # pin every matched block (incl. the COW source) BEFORE touching
        # the allocator: under pool pressure _alloc_blocks evicts
        # refcount-1 prefix entries, and without the pin it could evict
        # a block match() just returned and recycle it as "fresh" —
        # a duplicate id in the plan, i.e. the suffix prefill clobbering
        # its own cached prefix
        pinned = shared[:n_keep] + (shared[-1:] if cow else [])
        for b in pinned:
            self._alloc.ref(b)
        for book in self._sides:
            for b in sides:
                book.blocks.ref(b)
        new_ids = self._alloc_blocks(total - n_keep)
        if new_ids is None:
            self._deref_blocks(pinned)
            for book in self._sides:
                book.blocks.deref_many(sides)
            return None
        if cow:
            self._cow_copy(shared[-1], new_ids[0])
            self._alloc.deref(shared[-1])   # pinned only for the copy
            monitor.inc('kv_block_cow_total')
        monitor.inc('prefill_prompt_tokens_total', L)
        if self._prefix is not None:
            monitor.inc('kv_prefix_hit_total', labels={
                'outcome': 'hit' if ctx_len > 0 else 'miss'})
            if ctx_len > 0:
                monitor.inc('kv_prefix_tokens_saved_total', ctx_len)
        return shared[:n_keep] + new_ids, ctx_len, hashes, sides

    def _admit_one(self, req):
        """Admit one popped request. Returns False when it must wait for
        blocks (the request parks in _pending_admit and is retried every
        token boundary); True when the request was consumed — admitted,
        finished, or failed."""
        c = self.config
        if -(-req.prompt.size // c.block_size) > self._alloc.capacity:
            # no eviction can ever fit this prompt: structured
            # cache_full, zero tokens, nothing leaked
            monitor.inc('generate_request_total',
                        labels={'outcome': 'ok'})
            req._finish('cache_full')
            return True
        plan = self._paged_plan(req)
        if plan is None:
            self._pending_admit = req
            return False
        blocks, ctx_len, hashes, sides = plan
        table = self._slot_table(blocks)
        slot = self._free.pop()
        for book in self._sides:
            book.resume(slot, ctx_len // c.block_size, sides)
            if sides:
                monitor.inc(book.shared, len(sides))
                if book.resumed:
                    monitor.inc(book.resumed, ctx_len)
        if ctx_len > 0:
            for series, _layers in self._hits:
                monitor.inc(series)
        qs = max(0.0, time.monotonic() - req.enqueue_t)
        # queue wait as a histogram (the goodput 'queue' loss bucket
        # reads its sum) + the queue-SLO burn sentinel feed
        monitor.observe('generate_queue_seconds', qs)
        monitor.inc('generate_queue_wait_seconds_total', qs)
        monitor.inc('generate_admit_total')
        goodput.note_queue_wait(qs)
        if req.trace is not None:
            # queue stage closes at admission; the span rides the
            # SUBMITTER's tid so the trace shows the thread hop into
            # this decode loop
            req.trace.add_stage('queue', qs)
            monitor.record_span('request.queue', req.enqueue_wall,
                                qs * 1e6, tid=req._tid, trace=req.trace)
        return self._admit_run(_Admission(
            req, slot, blocks, table, hashes, int(ctx_len),
            (req.temperature, req.top_k, req.top_p, req._draw_u())))

    def _admit_run(self, adm):
        """The prefill of admission `adm` from `adm.off` on, and with
        its last dispatch the request resident. Behind a step in flight
        a prompt wider than the widest bucket takes ONE chunk a pass
        (`_chunking` keeps it for the next): the residents' step runs
        between two chunks, so a token gap holds one chunk and not the
        whole prompt's — a 4096-token prompt at a bucket of 512 held
        every other stream for eight chunks in one gap. With nothing to
        wait behind (the start, after an idle spell, the inline paths)
        and on a speculative engine every chunk goes now. Returns True:
        the request was consumed."""
        c = self.config
        req, slot, blocks = adm.req, adm.slot, adm.blocks
        dblocks, dtable = None, None
        # behind a step in flight the admission ends with its dispatch;
        # with nothing to wait behind the pass is the serial one, the
        # token now. So for a speculative engine: a round reads `last`
        # on the host
        defer = bool(self._flights) and not c.speculative
        done = False
        with _loop_phase('prefill', counted=False) as own:
            try:
                while not done:
                    start = adm.off
                    out, adm.off = self._prefill_dispatch(
                        req.prompt, start, adm.table, adm.sample,
                        self._prefill_bound, slot)
                    done = adm.off >= req.prompt.size
                    self._publish(adm, start)
                    if defer and not done:
                        break
                if done and c.speculative:
                    # the draft tracks the request in its OWN pool: full
                    # prompt (no prefix cache — draft K/V are
                    # model-specific throwaways), chunked exactly like the
                    # target's. With draft == target the prompt rows are
                    # block-copied from the target pool, not recomputed.
                    dblocks = self._draft_alloc.alloc(
                        -(-req.prompt.size // c.block_size))
                    if dblocks is None:     # unreachable by pool sizing
                        raise RuntimeError("draft KV pool exhausted")
                    dtable = self._slot_table(dblocks)
                    if self._draft_copies_target:
                        self._draft_cache_sync(dblocks, blocks)
                    else:
                        self._run_prefill(req.prompt, dtable,
                                          bound=self._draft_prefill_bound)
                if done and defer:
                    self._put_first(slot, out)
            except Exception as e:  # noqa: BLE001 — delivered per-request
                self._free.append(slot)
                self._deref_blocks(blocks)
                self._slot_books(slot)
                if dblocks:
                    self._draft_alloc.deref_many(dblocks)
                monitor.inc('generate_request_total',
                            labels={'outcome': 'error'})
                req.fail(e)
                out = None
        # the bound calls are the phases nested in it
        adm.self_s += own.dur_s - own.nested_s
        adm.dispatch_s += own.nested_s
        if out is None:
            _book_admission(adm.self_s, adm.dispatch_s)
            return True
        if not done:
            # the step dispatched next waits for the chunk before its own
            # fetch (`_pickup`): its time is then its own, and the gap
            # the chunk sits in reads as one that held an admission
            self._chunking = adm
            self._firsts.append(_First(slot, None, out, adm))
            return True
        st = _Slot(req, pos=req.prompt.size, blocks=blocks, table=adm.table,
                   dblocks=dblocks, dtable=dtable)
        st.first = _First(slot, st, out, adm)
        self._slots[slot] = st
        if defer:
            # the row joins the next step on its token as it is on the
            # device, and the loop picks the token up before that step's
            # fetch (`_step_complete`)
            self._firsts.append(st.first)
        else:
            self._pickup([st.first])
        self._set_occupancy()
        return True

    def _publish(self, adm, start):
        """Publish the FULL blocks of `adm`'s prompt that its dispatches
        have covered so far, the last of them from `start` on (immutable
        once prefilled: every later write lands strictly past them), each
        with the block that a bookkeeper's pool holds of the same rows, if
        it still does -- a ring has handed the earlier ones on by the
        prompt's end, so they go as the chunks do. The prefill may still be
        running: whatever reads the blocks is a program dispatched after
        it."""
        if self._prefix is None:
            return
        bs = self.config.block_size
        upto = min(adm.off // bs, len(adm.hashes))
        for book in self._sides:
            # a dispatch that ends on a block's edge leaves the slot's row
            # as the state there: a snapshot row takes it, for the entry of
            # the block that ends at the edge to hold
            if adm.off % bs == 0 and adm.published < upto \
                    and not self._prefix.has_side(adm.hashes[upto - 1]):
                book.snapshot(adm.slot, upto - 1)
        for i in range(adm.published, upto):
            side = None
            for book in self._sides:
                held = book.held(adm.slot, i)
                # a dispatch writes its last `reach` rows: one wider than
                # that leaves a gap behind the rows its predecessor wrote
                wrote = adm.off - book.reach
                first = max(0, (wrote if wrote > start
                                else start - book.reach) - i * bs)
                if held is not None and first < bs:
                    side = (held, first)
            self._prefix.register(adm.hashes[i], i, adm.blocks[i], side)
        adm.published = max(adm.published, adm.off // bs)
        for book in self._sides:
            self._copy_moved(book)

    def _drop_chunking(self, error, outcome):
        """The chunked admission under way ends here: its slot and blocks
        go back, its request fails with `error`."""
        adm, self._chunking = self._chunking, None
        if adm is None:
            return
        _book_admission(adm.self_s, adm.dispatch_s)
        self._free.append(adm.slot)
        self._deref_blocks(adm.blocks)
        self._slot_books(adm.slot)
        monitor.inc('generate_request_total', labels={'outcome': outcome})
        adm.req.fail(error)

    def _pickup(self, firsts):
        """The first tokens of `firsts` on the host, oldest first, each
        booked and emitted as the admission's end. Phase `prefill.fetch`
        is the time the loop BLOCKS for one (behind steps in flight the
        next step's time starts where the last of them lands:
        `_observe_step`). `prefill_seconds` is
        the loop's own time for the admission — that wait and what it
        took up to the dispatch, the phases `prefill` and
        `prefill.dispatch`, booked here with it: the whole stretch to
        the token with nothing in flight, its two ends behind a step in
        flight — where the request's `prefill` stage runs from the
        admission's start to here, across the passes in between. The
        engine's count of admissions moves, so the token gap that this
        wait is part of reads as one that held an admission
        (`_deliver`). A row evicted meanwhile (a deadline) is skipped,
        its token never fetched. A prefill that failed surfaces here:
        its request is failed, and with it every step and every
        admission dispatched since, once each — the cache is threaded
        through all of them (`_fail_step`)."""
        for n, f in enumerate(firsts):
            st, adm = f.st, f.adm
            if st is not None:
                # the loop's phase counters and prefill_seconds move for
                # an admission at ONE moment, this one: a window's two
                # deltas hold the same admissions
                _book_admission(adm.self_s, adm.dispatch_s)
                if self._slots[f.slot] is not st:
                    continue
            try:
                with _loop_phase('prefill.fetch') as alone:
                    if st is None:
                        np.asarray(f.out)       # the chunk's end
                    else:
                        st.last = int(self._split_load(f.out, 1)[0])
            except Exception as e:  # noqa: BLE001 — delivered per-request
                later = [g for g in firsts[n:] + self._firsts +
                         [g for fl in self._flights for g in fl.firsts]
                         if g.st is not None]
                # the failed one's own is booked above
                for g in later[1 if st is not None else 0:]:
                    _book_admission(g.adm.self_s, g.adm.dispatch_s)
                self._firsts = []
                for fl in self._flights:
                    fl.firsts = []
                if self._prefix is not None:
                    # it may hold blocks the failed prefill never wrote
                    self._prefix.drop_all()
                self._fail_step([(g.slot, g.st) for g in later], e,
                                *self._flights)
                return
            if st is None:
                # a chunk of an admission under way: no token, but the
                # device's time up to here was the prefill's and no
                # step's, and the gap it sits in held an admission
                adm.fetch_s += alone.dur_s
                self._admit_seq += 1
                if self._flights:
                    self._picked_t = time.perf_counter()
                continue
            r = st.req
            st.first = None
            now = time.perf_counter()
            if self._flights:
                self._picked_t = now
            pf_s = now - adm.t0
            monitor.observe('prefill_seconds', adm.self_s + adm.dispatch_s
                            + adm.fetch_s + alone.dur_s)
            if r.trace is not None:
                r.trace.add_stage('prefill', pf_s)
                monitor.record_span('request.prefill', adm.wall0,
                                    pf_s * 1e6, trace=r.trace)
            monitor.inc('decode_tokens_total')
            self._decode_tokens += 1
            r._emit(st.last)
            self._admit_seq += 1
            st.admit_seq = self._admit_seq
            st.last_t = now
            st.wall0 = time.time() * 1e6
            reason = self._finish_reason(st)
            if reason:
                # by `eos` the row may be in a step in flight already:
                # `_deliver` drops its result there
                self._release(f.slot)
                monitor.inc('generate_request_total',
                            labels={'outcome': 'ok'})
                r._finish(reason)
        self._set_occupancy()

    def _run_prefill(self, prompt, table, sample=(0.0, 0, 0.0, 0.0),
                     ctx_len=0, bound=None, slot=0):
        """Every dispatch of a prefill from `ctx_len` on, back to back;
        the last one's output, the model's answer."""
        bound = bound if bound is not None else self._prefill_bound
        off = int(ctx_len)
        while True:
            out, off = self._prefill_dispatch(prompt, off, table, sample,
                                              bound, slot)
            if off >= prompt.size:
                return out

    def _prefill_dispatch(self, prompt, off, table, sample, bound, slot):
        """One dispatch of a prefill: (its output, the position reached).
        Only the UN-CACHED suffix is computed; it buckets by
        suffix length — the prefill-compute saving of a prefix hit.
        A suffix wider than the widest bucket runs CHUNKED: each
        widest-bucket chunk deposits its K/V and attends the cached
        prefix (kv_prefix_attention), exactly like a shared-prefix
        suffix — same compiled signatures, any prompt length. Only
        the FINAL chunk's first-token output is the model's answer."""
        c = self.config
        # the dispatch's REAL rows: a chunk of the widest bucket while the
        # suffix is wider, then what is left of it
        rows = prompt[off:off + c.prompt_buckets[-1]]
        last = off + rows.size == prompt.size
        # the slot's own ring or row goes with it (the draft's prefills
        # are of a model that has none)
        self._slot_books(slot, off + rows.size, off)
        tables = self._tables_feed(table[None], [(0, slot)])
        for series, n_layers in self._prefill_rows:
            # the rows the layers' scans walk (a bucket's pad rows are not
            # among them)
            monitor.inc(series, rows.size * n_layers)
        if self._passes > 1:
            self._book_passes('prefill')
        if off > 0:
            # every dispatch that starts past position 0 — a hit's suffix,
            # a later chunk — resumes from a tail the pool holds, from the
            # slot's row instead of from zeros
            for series, _layers in self._resumes:
                monitor.inc(series)
        b = bucketize(rows.size, c.prompt_buckets)
        padded = np.full((1, b), c.pad_id, 'int64')
        padded[0, :rows.size] = rows
        pos = np.clip(off + np.arange(b), 0, c.max_len - 1)
        feed = {'gen_prompt': padded,
                'gen_pos': pos[None].astype('int64'),
                'gen_len': np.array([[rows.size]], 'int64')}
        feed.update(tables)
        feed.update(self._sample_feed(1, *(sample if last else ())))
        out = self._prefill_call(bound[b], feed)
        if last:
            # the copy to the host starts behind the prefill: by the
            # pick-up it is latency behind a busy device. (An earlier
            # chunk's K/V is deposited; its token output is never read.)
            out.copy_to_host_async()
        return out, off + rows.size

    def _prefill_call(self, bound, feed):
        """One prefill dispatch, phase `prefill.dispatch` inside
        `prefill` (`_admit_one` keeps their seconds for `_pickup`): the
        host's bound call and nothing more. The program queues behind
        the steps in flight on the device; its output stays there
        (`_run_prefill`, `_pickup`)."""
        with _loop_phase('prefill.dispatch', counted=False):
            return bound(feed, return_numpy=False)[0]

    def _step(self):
        """One decode step, dispatch + completion back to back (the
        inline/debug path; the engine loop splits the two so admission
        overlaps the device time). On a speculative engine with an
        all-greedy resident set this is one SPECULATIVE round."""
        if self._spec_ready():
            self._spec_round()
            return
        if self.config.speculative and \
                any(s is not None for s in self._slots):
            monitor.inc('spec_fallback_total')
            self._spec_fallbacks += 1
        pending = self._step_dispatch()
        if pending is not None:
            self._step_complete(pending)

    def _spec_ready(self):
        """Speculate this round? Requires a speculative engine, at
        least one resident, and every resident greedy (sampled rows
        have no argmax-identity acceptance rule — they fall back to
        plain steps)."""
        if not self.config.speculative:
            return False
        active = [s for s in self._slots if s is not None]
        return bool(active) and \
            all(s.req.temperature <= 0.0 for s in active)

    def _grow_blocks(self):
        """Pre-step pass: any resident whose next write position
        crosses into an unallocated block gets one more block; a dry
        pool (even after prefix-cache eviction) finishes the starved
        request with 'cache_full' and returns its blocks — neighbors
        keep decoding. Returns the slots this step leaves out: those
        with a token still to come — of a step in flight (`ahead`), or
        the first, of the prefill (`first`) — that the host can see
        ending at its delivery — `length`, `max_len` — so that no row is
        computed for nothing, and those the dry pool starves while that
        token is still to come (the next pass decides)."""
        c = self.config
        bs = c.block_size
        held = set()
        for i, st in enumerate(self._slots):
            if st is None:
                continue
            at = st.pos + st.ahead
            unseen = st.ahead or st.first is not None
            if unseen and (at >= c.max_len or st.generated + st.ahead
                           >= st.req.max_new_tokens):
                held.add(i)
                continue
            if at // bs < len(st.blocks):
                continue
            grown = self._alloc_blocks(1)
            if grown is None:
                if unseen:
                    held.add(i)
                    continue
                self._release(i)
                monitor.inc('generate_request_total',
                            labels={'outcome': 'ok'})
                st.req._finish('cache_full')
                continue
            st.table[len(st.blocks)] = grown[0]
            st.blocks.append(grown[0])
        self._set_occupancy()
        return held

    # ------------------------------------------------------------------
    # speculative decode
    def _spec_grow(self, active):
        """Pre-round block growth for speculation: per active slot,
        extend the TARGET table to cover the verify window's write
        positions (pos .. pos + spec_k, capped at max_len - 1) and the
        DRAFT table to the SAME coverage — the drafter's trailing
        write-only tower deposits position pos + spec_k too, and
        trashing that row would silently drop a target-equal draft's
        accept rate below 1.0.
        Returns {slot_index: n_valid} — how many verify rows are fully
        budgeted (cache coverage, max_len, AND the request's remaining
        max_new_tokens: proposals past what the request may still emit
        are never counted, so accept_rate measures draft QUALITY, not
        budget clipping). Target tail blocks that
        end up holding no accepted position are returned to the pool by
        the post-verify truncation; a pool too dry to extend the tail
        just shortens this round's window (n_valid >= 1 always — the
        plain `_grow_blocks` already guaranteed the next write's
        block), it never starves a request."""
        c = self.config
        bs = c.block_size
        K = c.spec_k
        n_valid = {}
        for i, st in active:
            want_last = min(st.pos + K, c.max_len - 1) // bs
            while len(st.blocks) <= want_last:
                grown = self._alloc_blocks(1)
                if grown is None:
                    break
                st.table[len(st.blocks)] = grown[0]
                st.blocks.append(grown[0])
            covered = len(st.blocks) * bs - 1       # last writable pos
            remaining = st.req.max_new_tokens - st.generated
            n_valid[i] = max(1, min(K + 1, c.max_len - st.pos,
                                    covered - st.pos + 1, remaining))
            # draft coverage mirrors the target's: the trailing
            # write-only draft step deposits position pos + K too
            dwant_last = want_last
            while len(st.dblocks) <= dwant_last:
                grown = self._draft_alloc.alloc(1)
                if grown is None:       # unreachable by pool sizing
                    break
                st.dtable[len(st.dblocks)] = grown[0]
                st.dblocks.append(grown[0])
        self._set_block_gauges()
        return n_valid

    def _spec_truncate(self, st):
        """Roll back the speculative tail: blocks holding NO position
        below the slot's accepted write head — and not needed for the
        NEXT write either — return to their pools and their table
        entries zero out (the trash block). No cache bytes move —
        rejected rows sit past the write head where every attention
        masks them to exact zero. Keeping the next-write block (not
        just ceil(pos/bs)) matches the plain path's invariant that a
        resident never releases the block its next token lands in:
        when an accept ends exactly on a block boundary, freeing that
        block would let a competing slot grab it and turn this
        request's next growth into a premature 'cache_full'."""
        bs = self.config.block_size
        keep = min(self._max_blocks, st.pos // bs + 1)
        while len(st.blocks) > keep:
            b = st.blocks.pop()
            st.table[len(st.blocks)] = 0
            self._alloc.deref(b)
        while len(st.dblocks) > keep:
            b = st.dblocks.pop()
            st.dtable[len(st.dblocks)] = 0
            self._draft_alloc.deref(b)

    def _spec_round(self):
        """One speculative decode round over the resident (all-greedy)
        slots: ONE drafter dispatch proposes spec_k tokens per slot
        from the draft model's paged cache, ONE verify dispatch scores
        all spec_k + 1 positions with the target, and the host accepts
        the longest draft prefix the target agrees with plus the
        target's own next token — every emitted token is the target's
        argmax given the previously emitted tokens, so the output
        stream is bitwise the non-speculative greedy stream. Rejected
        rows roll back via block-table truncation."""
        c = self.config
        self._grow_blocks()     # plain growth (may starve -> cache_full)
        active = [(i, st) for i, st in enumerate(self._slots)
                  if st is not None]
        if not active:
            return
        K, W, S, MB = c.spec_k, c.spec_k + 1, c.slots, self._max_blocks
        n_valid = self._spec_grow(active)
        if max(n_valid.values()) <= 1:
            # every resident is one token from its budget/cache edge —
            # nobody can consume a proposal, so a plain step is
            # strictly cheaper than draft + verify this round
            pending = self._step_dispatch()
            if pending is not None:
                self._step_complete(pending)
            return

        # --- draft-cache staleness: fallback rounds (a sampled rider
        # pinning the batch onto plain steps) advanced positions with
        # K/V deposited into the TARGET cache only. Resuming speculation
        # against those draft-cache holes is CORRECT (acceptance is the
        # target's argmax identity) but accept-degraded — count the
        # resume, and on the draft==target path resync by block-copying
        # the slot's current target blocks across pools (_spec_grow just
        # extended the draft table to the same coverage; the same jitted
        # fixed-width scatter the admission sync uses — zero recompiles).
        # A distinct draft model has no valid copy source (its K/V are
        # model-specific); its stale rows age out only as its own
        # drafter writes past them, which the counter makes visible.
        stale = [(i, st) for i, st in active if st.draft_stale]
        if stale:
            monitor.inc('spec_stale_draft_rounds_total')
            self._spec_stale_rounds += 1
            for i, st in stale:
                if self._draft_copies_target:
                    nsync = min(len(st.dblocks), len(st.blocks))
                    if nsync:
                        self._draft_cache_sync(st.dblocks[:nsync],
                                               st.blocks[:nsync])
                st.draft_stale = False

        # --- draft: K unrolled greedy steps, one dispatch -------------
        # (feed construction vectorized over the slot axis — this runs
        # once per ~K+1 emitted tokens and must stay off the host
        # critical path's per-token budget)
        t0 = time.perf_counter()
        wall0 = time.time() * 1e6
        idx = np.array([i for i, _ in active])
        lastv = np.array([st.last for _, st in active], 'int64')
        posv = np.array([st.pos for _, st in active], 'int64')
        toks = np.zeros((S, 1), 'int64')
        pos = np.zeros((S, 1), 'int64')
        dbtab = np.zeros((S, MB), 'int64')
        vb = np.zeros((S, MB), 'int64')
        toks[idx, 0] = lastv
        pos[idx, 0] = posv
        for i, st in active:
            dbtab[i] = st.dtable
            vb[i] = st.table
        dlim = np.array([min(c.max_len, len(st.dblocks) * c.block_size)
                         for _, st in active], 'int64')
        dvm = np.zeros((S, K + 1), 'int64')
        dvm[idx] = np.arange(K + 1)[None, :] < \
            np.clip(dlim - posv, 0, K + 1)[:, None]
        try:
            douts = self._drafter_bound({
                'gen_tokens': toks, 'gen_pos': pos, 'gen_btab': dbtab,
                'gen_vmask': dvm})
            drafts = np.asarray(douts[0]).reshape(S, K)
        except Exception as e:  # noqa: BLE001 — delivered per-request
            self._fail_step(active, e)
            return
        draft_s = time.perf_counter() - t0

        # --- verify: one (K+1)-wide target step -----------------------
        t1 = time.perf_counter()
        vt = np.zeros((S, W), 'int64')
        vp = np.zeros((S, W), 'int64')
        vv = np.zeros((S, W), 'int64')
        vt[idx, 0] = lastv
        vt[idx, 1:] = drafts[idx]
        vp[idx] = np.clip(posv[:, None] + np.arange(W)[None, :], 0,
                          c.max_len - 1)
        nvs = np.array([n_valid[i] for i, _ in active], 'int64')
        vv[idx] = np.arange(W)[None, :] < nvs[:, None]
        try:
            out = self._verify_bound({
                'gen_tokens': vt, 'gen_pos': vp, 'gen_btab': vb,
                'gen_vmask': vv}, return_numpy=False)
        except Exception as e:  # noqa: BLE001 — delivered per-request
            self._fail_step(active, e)
            return
        # overlap: admit queued prompts while the verify computes
        with _loop_phase('admit_overlapped'):
            t_adm = time.perf_counter()
            self._admit()
            adm_s = time.perf_counter() - t_adm
        try:
            verdict = np.asarray(out[0]).reshape(S, W)
        except Exception as e:  # noqa: BLE001 — delivered per-request
            self._fail_step(active, e)
            return
        verify_s = max(0.0, time.perf_counter() - t1 - adm_s)
        monitor.observe('spec_draft_seconds', draft_s)
        monitor.observe('spec_verify_seconds', verify_s)
        monitor.observe('decode_step_seconds', draft_s + verify_s)

        # --- accept + rollback ----------------------------------------
        now = time.perf_counter()
        self._decode_steps += 1
        round_proposed = round_accepted = emitted_total = 0
        # longest draft prefix the target's argmax agrees with, per slot
        agree = drafts[idx] == verdict[idx, :K]              # [n, K]
        first_miss = np.argmax(~agree, axis=1)
        runs = np.where(agree.all(axis=1), K, first_miss)
        run_by_slot = dict(zip(idx.tolist(), runs.tolist()))
        for i, st in active:
            r = st.req
            nv = n_valid[i]
            proposed = nv - 1
            m = 1 + min(run_by_slot[i], nv - 1)
            m = min(m, r.max_new_tokens - st.generated)
            emitted = [int(verdict[i, t]) for t in range(m)]
            if c.eos_id is not None and c.eos_id in emitted:
                emitted = emitted[:emitted.index(c.eos_id) + 1]
                m = len(emitted)
            accepted = max(0, m - 1)
            round_proposed += proposed
            round_accepted += accepted
            r.spec_proposed += proposed
            r.spec_accepted += accepted
            st.pos += m
            st.generated += m
            st.last = emitted[-1]
            self._spec_truncate(st)
            dt = max(0.0, now - st.last_t)
            st.last_t = now
            # a round's gaps are in no generate_token_gap* series; the
            # slot's count stays that of its last token
            st.admit_seq = self._admit_seq
            if r.trace is not None:
                # draft/verify are SUB-stages of the decode wall: the
                # residual host time stays in decode_step so the stage
                # sum still composes the request's end-to-end latency
                r.trace.add_stage('draft', draft_s)
                r.trace.add_stage('verify', verify_s)
                r.trace.add_stage('decode_step',
                                  max(0.0, dt - draft_s - verify_s))
                monitor.record_span('request.draft', wall0,
                                    draft_s * 1e6, trace=r.trace)
                monitor.record_span('request.verify',
                                    wall0 + draft_s * 1e6,
                                    verify_s * 1e6, trace=r.trace)
            per_tok = dt / m
            for tok in emitted:
                r.step_s.append(per_tok)
                r._emit(tok)
            emitted_total += m
            reason = self._finish_reason(st)
            if reason:
                self._release(i)
                monitor.inc('generate_request_total',
                            labels={'outcome': 'ok'})
                if r.trace is not None and r.trace.sampled and r.step_s:
                    monitor.record_span('request.decode', st.wall0,
                                        sum(r.step_s) * 1e6,
                                        trace=r.trace)
                r._finish(reason)
        self._decode_tokens += emitted_total
        monitor.inc('decode_tokens_total', emitted_total)
        monitor.inc('spec_propose_total', round_proposed)
        monitor.inc('spec_accept_total', round_accepted)
        if round_proposed:
            # accept-collapse sentinel feed (perf_regression_total
            # {kind=accept_collapse} when the EWMA falls off its baseline)
            goodput.note_accept(round_accepted / float(round_proposed),
                                model='generate')
        self._spec_rounds += 1
        self._spec_proposed += round_proposed
        self._spec_accepted += round_accepted
        self._occ_sum += len(active) / float(c.slots)
        self._set_block_gauges()
        self._set_occupancy()

    def _fallback_dispatch(self, prev=None):
        """The loop's plain step. On a speculative engine it is a
        fallback — a sampled resident pins the whole batch on plain steps
        (speculation accelerates greedy traffic: acceptance is an argmax
        identity) — and is counted as one; such steps pipeline like any
        other, and the round that follows the last of them starts with
        nothing in flight."""
        if self.config.speculative:
            monitor.inc('spec_fallback_total')
            self._spec_fallbacks += 1
        return self._step_dispatch(prev)

    def _step_dispatch(self, prev=None):
        """Snapshot the resident slots and dispatch one decode step
        WITHOUT materializing its next-token fetch — JAX's async
        dispatch returns as soon as the step is staged, so the caller
        can do host work while the device computes. With `prev`, the
        step dispatched before this one and not fetched yet, a row of
        `prev` takes its input token from `prev`'s output on the device
        (`_stage_feeds`) and writes one position further; a row
        admitted since takes its prefill's first token, on the device as
        well (`generate_first_token_carried_total`); every other row is
        fed from the host as without. Returns the step's `_Flight`, or
        None with nothing to step or after a failure."""
        with _loop_phase('feed'):
            c = self.config
            held = self._grow_blocks()
            S = c.slots
            toks = np.zeros((S, 1), 'int64')
            # where a row's token is: 0 the host, 1 `prev`'s output, 2
            # the first tokens' buffer
            src = np.zeros((S, 1), 'int8')
            pos = np.zeros((S, 1), 'int64')
            sample = self._sample_feed(S)
            btab = np.zeros((S, self._max_blocks), 'int64')
            active = []
            live_pages = live_tokens = carried = 0
            for i, st in enumerate(self._slots):
                if st is None or i in held:
                    continue
                if st.ahead:
                    src[i] = 1
                elif st.first is not None:
                    src[i] = 2
                    carried += 1
                else:
                    toks[i] = st.last
                at = st.pos + st.ahead
                pos[i] = at
                r = st.req
                sample['gen_temp'][i] = r.temperature
                sample['gen_topk'][i] = r.top_k
                sample['gen_topp'][i] = r.top_p
                # drawn a step ahead of the token it follows; a row
                # whose result is dropped belongs to a request that ended
                sample['gen_u'][i] = r._draw_u()
                btab[i] = st.table
                live_pages += at // c.block_size + 1
                live_tokens += at + 1
                active.append((i, st))
            # the admissions since the last dispatch: this step's to pick
            # up, whether or not their rows are in it
            firsts, self._firsts = self._firsts, []
            if not active:
                # no step to wait behind: now
                self._pickup(firsts)
                return None
            if (sample['gen_temp'] > 0).any():
                # the very condition sample_next_token branches on: one
                # sampled row puts the whole step on its sampled branch
                # (the sort and three passes over [slots, vocab]); an
                # all-greedy step is the argmax
                self._sampled_steps += 1
                monitor.inc('generate_sampled_steps_total')
            # what the step's paged attention reads of what its tables
            # span (stats()['blocks']['decode_live_page_share'])
            monitor.inc('kv_decode_pages_live_total', live_pages)
            monitor.inc('kv_decode_pages_table_total',
                        len(active) * self._max_blocks)
            if self._books:     # pools a slot owns: their books advance
                lengths = [int(pos[i, 0]) + 1 for i, _ in active]
                for (i, _), n in zip(active, lengths):
                    self._slot_books(i, n)
            for (series, most), n_layers in self._step_reads:
                # the rows the step has to read of a pool's layers: the
                # latent rows, or the per-head K and V rows of the layers
                # that see every key (`most` None: all up to the slot's
                # position); of a pool a slot owns (so `lengths` is made),
                # a window's worth at most, or the ONE state row the step
                # reads, advances and writes back
                rows = n_layers * (live_tokens if most is None
                                   else sum(min(n, most) for n in lengths))
                monitor.inc(series, rows)
                if self._passes > 1 and monitor.tracing() is not None:
                    # ... and apart while a profiler session is live: what
                    # the steps of a trace read, beside the trace's own
                    # kernel seconds (`stats()['passes']['traced']`)
                    self._traced_reads[series] = \
                        self._traced_reads.get(series, 0) + rows
            feed = {'gen_pos': pos}
            feed.update(self._tables_feed(btab, ((i, i) for i, _ in active)))
        with _loop_phase('dispatch'):
            t0 = time.perf_counter()
            try:
                feed = self._stage_feeds(
                    None if prev is None else prev.out[0], src, toks, feed)
                feed.update(sample)
                out = self._step_bound(feed, return_numpy=False)
                # the device-to-host copy starts now and is latency
                # behind the next step, not a wait after this one
                out[0].copy_to_host_async()
            except Exception as e:  # noqa: BLE001 — delivered per-request
                self._fail_step(active, e, prev)
                # those the failure left (a row this step held back)
                self._pickup(firsts)
                return None
        if carried:
            self._first_carried += carried
            monitor.inc('generate_first_token_carried_total', carried)
        for _i, st in active:
            st.ahead += 1
        flight = _Flight(out, active, t0, prev is not None, firsts)
        self._flights.append(flight)
        return flight

    def _fail_step(self, active, e, *flights):
        """An exhausted retry (or permanent fault) fails the RESIDENT
        requests; the loop and the engine live on — the decode analog of
        the PR 4 "pool never dies" contract. The steps in flight go with
        it, their residents failed once each: the cache is threaded
        through every step, so what a later one computed on a failed
        one's state is nobody's token."""
        monitor.inc('generate_step_error_total')
        active = list(active)
        for f in flights:
            if f is not None:
                active += f.active
                f.out = None
                if f in self._flights:
                    self._flights.remove(f)
        blackbox.record('generate_step_error', error=e,
                        program=getattr(self._step_bound, '_program', None),
                        residents=len(active))
        for i, st in active:
            if self._slots[i] is not st:
                continue    # failed a line above, or gone before
            self._release(i)
            monitor.inc('generate_request_total',
                        labels={'outcome': 'error'})
            st.req.fail(e)
        # ... and the prefill between two of its chunks
        self._drop_chunking(e, 'error')
        self._set_occupancy()

    def _step_complete(self, flight, nxt=None):
        """Fetch step `flight`'s tokens and deliver them; `nxt` is the
        step dispatched behind it. A step that fails here takes `nxt`
        with it: the residents of both are failed, neither delivers."""
        firsts, flight.firsts = flight.firsts, []
        # a stream sees its first token before its second: the first
        # tokens this step was dispatched on, picked up before its own
        self._pickup(firsts)
        if flight.out is None:
            return      # went with a failed dispatch or a failed prefill
        try:
            # materialization = device completion; an async runtime
            # failure surfaces here and fails the residents
            with _loop_phase('wait'):
                fetched = flight.fetch()
                self._observe_step(flight)
        except Exception as e:  # noqa: BLE001 — delivered per-request
            self._fail_step([], e, flight, nxt)
            return
        self._flights.remove(flight)
        with _loop_phase('deliver'):
            self._deliver(flight.active,
                          self._split_load(fetched, self.config.slots))
        with _loop_phase('yield'):
            # The threads that consume the tokens run NOW: with the
            # pipeline full this thread no longer blocks on the device,
            # and a consumer would otherwise get the GIL a switch
            # interval later, in the middle of the next dispatch — a
            # client whose request just ended then sends its next one
            # too late for the coming admission (on the chip: chat's
            # ttft_p95_ms + 14 % without this line, PERF.md PR 31). The
            # work is theirs either way; this only says when, and the
            # phase keeps their turn out of `deliver`, the loop's own.
            time.sleep(0)

    def _observe_step(self, flight):
        """decode_step_seconds, one observation a decode step. A step
        that was in flight together with the one before it is timed from
        that one's fetch to its own: from its own dispatch it would read
        two periods, round a fetch that is already there nothing. Where
        first tokens were picked up in between (`_pickup`, just before
        this fetch), it is timed from the last of them: the device ran
        the step before, the prefills, then this step, so the stretch
        from that step's fetch to the first tokens on the host was the
        prefills' and what follows is this step's. (The loop's wait for
        them alone, `prefill.fetch`, is shorter by the host's own work
        since the last fetch: taken out in its place it left that work
        in the step — + 4 to 9 % on the mean at 64 rows. PERF.md, PR
        38.) A step with no predecessor in flight is timed from its
        dispatch. In a device-bound loop the mean is the device's step;
        in a host-bound one the loop's period, cut short wherever a pass
        picked a first token up."""
        now = time.perf_counter()
        since = max(self._fetched_t if flight.overlapped else flight.t0,
                    self._picked_t)
        monitor.observe('decode_step_seconds', max(0.0, now - since))
        self._fetched_t = now
        if flight.overlapped:
            # booked with the observation it is a share of, so that a
            # window's two deltas count the same steps
            self._overlapped_steps += 1
            monitor.inc('generate_overlapped_steps_total')

    def _deliver(self, active, tokens):
        """The host's share of a completed step: per-slot bookkeeping,
        the tokens out to their streams, finished slots released. A row
        is booked only for the tenant it was dispatched for: one that
        finished by `eos` (or was evicted) with this step in flight
        already is gone, its row's result dropped and counted."""
        now = time.perf_counter()
        live = [(i, st) for i, st in active if self._slots[i] is st]
        n = len(live)
        if n < len(active):
            self._discarded_rows += len(active) - n
            monitor.inc('generate_discarded_rows_total', len(active) - n)
        self._decode_steps += 1
        self._decode_tokens += n
        self._occ_sum += n / float(self.config.slots)
        monitor.inc('decode_tokens_total', n)
        speculative = self.config.speculative
        # every token gap by whether an admission completed inside it:
        # the engine's count moved since the row's last token
        seq = self._admit_seq
        held_s = plain_s = 0.0
        held_n = 0
        for i, st in live:
            st.ahead -= 1
            st.pos += 1
            st.generated += 1
            st.last = int(tokens[i])
            if speculative:
                # this plain step wrote position pos-1 into the TARGET
                # cache only; the draft cache now has a hole there
                st.draft_stale = True
            # per-request inter-token gap (WALL, overlap included): these
            # compose the request's 'decode_step' stage so queue +
            # prefill + decode sums to its end-to-end latency
            dt = max(0.0, now - st.last_t)
            st.last_t = now
            if st.admit_seq != seq:
                st.admit_seq = seq
                held_s += dt
                held_n += 1
                st.req.admission_wait_s += dt
                st.req.admissions_waited += 1
            else:
                plain_s += dt
            if st.req.trace is not None:
                st.req.trace.add_stage('decode_step', dt)
                st.req.step_s.append(dt)
            st.req._emit(st.last)
            reason = self._finish_reason(st)
            if reason:
                self._release(i)
                monitor.inc('generate_request_total',
                            labels={'outcome': 'ok'})
                if st.req.trace is not None and st.req.trace.sampled \
                        and st.req.step_s:
                    monitor.record_span('request.decode', st.wall0,
                                        sum(st.req.step_s) * 1e6,
                                        trace=st.req.trace)
                st.req._finish(reason)
        if held_n:
            monitor.inc('generate_token_gap_seconds_total', held_s, _HELD)
            monitor.inc('generate_token_gaps_total', held_n, _HELD)
        if n > held_n:
            monitor.inc('generate_token_gap_seconds_total', plain_s, _PLAIN)
            monitor.inc('generate_token_gaps_total', n - held_n, _PLAIN)
        self._set_occupancy()

    def _finish_reason(self, st):
        c = self.config
        if c.eos_id is not None and st.last == c.eos_id:
            return 'eos'
        if st.generated >= st.req.max_new_tokens:
            return 'length'
        if st.pos >= c.max_len:
            # the cache has no row left for this token's K/V — stepping
            # further would attend past the buffer
            return 'cache_full'
        return None

    def _evict_expired(self):
        now = time.monotonic()
        for i, st in enumerate(self._slots):
            if st is not None and st.req.expired(now):
                self._release(i)
                monitor.inc('generate_request_total',
                            labels={'outcome': 'deadline'})
                st.req.fail(DeadlineExceededError(
                    "deadline passed mid-generation after %d tokens"
                    % st.generated))
        if self._pending_admit is not None and \
                self._pending_admit.expired(now):
            req, self._pending_admit = self._pending_admit, None
            monitor.inc('generate_request_total',
                        labels={'outcome': 'deadline'})
            req.fail(DeadlineExceededError(
                "deadline passed waiting for free KV blocks"))
        self._set_occupancy()

    def _fail_expired(self, expired):
        now = time.monotonic()
        for r in expired:
            monitor.inc('generate_request_total',
                        labels={'outcome': 'deadline'})
            r.fail(DeadlineExceededError(
                "deadline passed after %.3fs in queue"
                % (now - r.enqueue_t)))

    def _release(self, i):
        # The step in flight may hold this slot's row still (a finish by
        # `eos`, an eviction): it then writes one stale K/V row into a
        # block handed back here — one past the prompt, so never a block
        # the prefix cache shares. The device runs programs in dispatch
        # order and the cache is threaded through them as donated state,
        # so whatever a later tenant of the block writes lands after, and
        # rows past a tenant's own write head are masked in every
        # attention: the stale row is never read.
        st = self._slots[i]
        if st is not None:
            self._release_blocks(st)
            # its ring's blocks go back too, but for those the prefix cache
            # or another tenant holds: the stale row lands in the block of
            # the departed tenant's own last position, which nobody shares
            self._slot_books(i)
            # So for its row in the state-space layers' pools: the step in
            # flight advances the departed tenant's state once more, in a
            # row nothing reads until the next tenant's first chunk, which
            # starts at position 0 FROM ZEROS (ops/ssm_ops.py never reads
            # the row there) and writes the whole row, after that step in
            # dispatch order. A later chunk resumes from what the first
            # wrote (tests/test_jamba_serving.py holds both).
        self._slots[i] = None
        self._free.append(i)

    def _set_occupancy(self):
        n = sum(1 for s in self._slots if s is not None)
        occ = n / float(len(self._slots))
        self._occ_peak = max(self._occ_peak, occ)
        self._active_peak = max(self._active_peak, n)
        monitor.set_gauge('kv_slot_occupancy', occ)

    # ------------------------------------------------------------------
    def stats(self):
        """Decode-loop statistics since construction. 'blocks' is the
        block-level capacity accounting — physical pool state, the
        peak footprint, and the prefix-cache entry count (the monitor
        mirrors it as kv_blocks_in_use/free), and the share of table
        pages the decode steps read; it is the GLOBAL layers' pool, and
        'window' in it the window layers' (`WindowRings`). 'state' (a
        model with state-space layers) is their pools' rows, one a slot
        that is admitted (both: the bookkeepers' `report`). 'passes' (a
        looped model, `LMConfig.passes`): the passes a token takes, those
        run by phase, the decode steps' exit mass a pass summed over
        their live rows (`_book_passes`), and 'traced', by series, the rows
        the steps dispatched under a live profiler session read of the
        pools' layers -- the bytes that belong to a trace's own kernel
        seconds. 'loop' is where
        the loop thread's time went, by phase (_loop_sums)."""
        steps = self._decode_steps
        out = {
            'slots': self.config.slots,
            'active': sum(1 for s in self._slots if s is not None),
            'peak_active': self._active_peak,
            'queue_depth': self.queue.depth(),
            'decode_steps': steps,
            'sampled_steps': self._sampled_steps,
            'overlapped_steps': self._overlapped_steps,
            'discarded_rows': self._discarded_rows,
            'first_tokens_carried': self._first_carried,
            # how often one of the engine's bound programs staged its
            # weights again because the scope was written: 0 in steady
            # serving, + 1 a handle after a weight is rebound
            'bound_restages': sum(b.restages for b in self._handles),
            # leaves a handle's staging laid out anew, as its compiled
            # entry wants them: at warmup or after a rebind, never in
            # steady serving
            'bound_relayouts': sum(b.relayouts for b in self._handles),
            'decode_tokens': self._decode_tokens,
            'peak_slot_occupancy': round(self._occ_peak, 4),
            'mean_slot_occupancy': round(self._occ_sum / steps, 4)
            if steps else 0.0,
        }
        out['blocks'] = {
            'block_size': self.config.block_size,
            'capacity': self._alloc.capacity,
            'in_use': self._alloc.in_use(),
            'free': self._alloc.available(),
            'peak_in_use': self._blocks_peak,
            'prefix_entries': len(self._prefix)
            if self._prefix is not None else 0,
            'decode_live_page_share': _live_page_share(),
        }
        for book in self._books:
            book.report(out)
        if self._passes > 1:
            out['passes'] = {'a_token': self._passes,
                             'run': dict(self._pass_runs),
                             'exit_mass': list(self._exit_mass),
                             'traced': dict(self._traced_reads)}
        if self.config.speculative:
            prop = self._spec_proposed
            out['spec'] = {
                'k': self.config.spec_k,
                'rounds': self._spec_rounds,
                'fallback_rounds': self._spec_fallbacks,
                'stale_draft_rounds': self._spec_stale_rounds,
                'proposed': prop,
                'accepted': self._spec_accepted,
                'accept_rate': round(self._spec_accepted / float(prop), 4)
                if prop else 0.0,
                'draft_blocks_in_use': self._draft_alloc.in_use(),
            }
        out['loop'] = _loop_sums()
        out['goodput'] = goodput.stats(fps=self._goodput_fp_set())
        return out

    def _goodput_fp_set(self):
        """Fingerprints of every program this engine dispatches (decode
        step, per-bucket prefills, drafter/verify/draft-prefills) — the
        filter for the engine-scoped stats()['goodput'] block. Memoized:
        the program set is fixed at construction."""
        if self._goodput_fps is None:
            progs = [self._step_prog] + \
                [p for p, _ in self._prefill.values()]
            if self.config.speculative:
                progs += [self._drafter_prog, self._verify_prog]
                progs += [p for p, _ in self._draft_prefill.values()]
            fps = set()
            for p in progs:
                fp = p._fingerprint()
                fps.add(fp)
                goodput.name_model(fp, 'generate')
            self._goodput_fps = fps
        return self._goodput_fps
