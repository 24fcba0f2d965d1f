"""Fault-tolerant runtime layer: fault injection, retry/backoff, hardened
checkpoint primitives, and non-finite-step recovery.

The reference Fluid runtime survives real fleets through PADDLE_ENFORCE
error chains, parameter-server retry loops, and checkpoint_notify
(operators/checkpoint_notify_op.cc); its TPU-native rebuild compiles and
observes well but — before this layer — died on the first transient
compile failure, corrupted checkpoint, hung rendezvous, or NaN step.
Four cooperating pieces:

- **Fault injection** (``PADDLE_FAULT_SPEC``): raise controlled
  ``InjectedFault`` errors at the compile / run / host_relay / collective /
  checkpoint-write / checkpoint-restore boundaries so every recovery path
  below is actually testable. Grammar (';'-separated clauses)::

      site:trigger[,kind=fatal]
      compile:p=0.5        # each compile fails with probability 0.5
      run:nth=3            # exactly the 3rd run dispatch fails
      run:n=2              # the first 2 dispatches fail (then recover)
      ckpt_write:always    # every checkpoint write fails
      ckpt_restore:nth=1   # the newest checkpoint fails to restore
      collective:every=4   # every 4th collective boundary fails

  Faults are transient (retryable) unless ``kind=fatal``. The env var is
  re-read at every site check, so tests can flip it mid-process.

- **Retry policy**: exponential backoff + full jitter + a wall-clock
  deadline, applied by the executor to transient compile/dispatch errors
  (RESOURCE_EXHAUSTED, UNAVAILABLE, connection resets — the TF-style
  transient classes) and by the distributed bootstrap to rendezvous.
  Knobs: ``PADDLE_RETRY_MAX_ATTEMPTS`` (default 4), ``PADDLE_RETRY_BASE_S``
  (0.05), ``PADDLE_RETRY_MAX_S`` (2.0), ``PADDLE_RETRY_DEADLINE_S`` (30).

- **Checkpoint hardening helpers** (crc32 manifests, atomic tmp+fsync+
  rename writes) used by checkpoint.py / io.py; see
  ``checkpoint.load_latest_valid`` for the fallback-restore contract.

- **TrainingGuard**: a step wrapper that detects a non-finite loss, rolls
  the scope back to the pre-step state, backs off an optional loss scale,
  and escalates to a raise after N consecutive bad steps.

- **elastic_train_loop**: the preemption-aware driver — on a worker loss
  (``WorkerFailedError``), a TrainingGuard escalation (``NonFiniteError``)
  or a fatal injected fault (the chaos-drill stand-in for a kill), it
  rebuilds a mesh from the surviving device set, restores the latest
  valid checkpoint **resharded onto it** (checkpoint.py ``mesh=`` path)
  and replays from the checkpointed step instead of dying.

Every recovery event increments a monitor counter (``retry_attempt_total``
``{site}``, ``retry_giveup_total{site}``, ``fault_injected_total{site}``,
``ckpt_fallback_total``, ``nonfinite_skip_total``) so the observability
layer answers "is this job limping" without a debugger. Full catalog:
docs/resilience.md.
"""
import os
import random
import threading
import time
import zlib

import numpy as np

from . import blackbox
from . import monitor
from . import trace as trace_mod

__all__ = ['InjectedFault', 'NonFiniteError', 'RetryPolicy', 'TrainingGuard',
           'maybe_fault', 'install_fault', 'clear_faults', 'fault_spec',
           'is_transient', 'retry_call', 'retry_after',
           'elastic_train_loop']


# ---------------------------------------------------------------------------
# fault injection


class InjectedFault(RuntimeError):
    """Controlled fault raised at a runtime boundary by PADDLE_FAULT_SPEC /
    install_fault. Transient by default so the retry layer engages; fatal
    faults (kind=fatal) must propagate un-retried."""

    def __init__(self, site, message, transient=True):
        RuntimeError.__init__(self, message)
        self.site = site
        self.transient = transient


class NonFiniteError(RuntimeError):
    """Raised by TrainingGuard after max_bad_steps consecutive non-finite
    steps — the escalation path when skipping stops being recovery and
    starts being denial."""


class _FaultRule(object):
    __slots__ = ('site', 'mode', 'value', 'fatal', 'calls', 'rng')

    def __init__(self, site, mode, value, fatal):
        self.site = site
        self.mode = mode          # 'always' | 'p' | 'nth' | 'n' | 'every'
        self.value = value
        self.fatal = fatal
        self.calls = 0
        # deterministic per-rule stream: reproducible fault schedules
        # without perturbing global random state
        seed = int(os.environ.get('PADDLE_FAULT_SEED', '0') or 0)
        self.rng = random.Random((zlib.crc32(site.encode()) << 1) ^ seed)

    def fire(self):
        self.calls += 1
        if self.mode == 'always':
            return True
        if self.mode == 'p':
            return self.rng.random() < self.value
        if self.mode == 'nth':
            return self.calls == int(self.value)
        if self.mode == 'n':
            return self.calls <= int(self.value)
        if self.mode == 'every':
            return self.calls % int(self.value) == 0
        return False


def _parse_spec(spec):
    """'compile:p=0.5;run:nth=3,kind=fatal' -> {site: _FaultRule}. Raises
    ValueError on a malformed clause — a typo'd fault spec silently doing
    nothing would defeat the whole point of injecting faults."""
    rules = {}
    for clause in spec.split(';'):
        clause = clause.strip()
        if not clause:
            continue
        if ':' not in clause:
            raise ValueError(
                "PADDLE_FAULT_SPEC clause %r: expected 'site:trigger'"
                % clause)
        site, _, rest = clause.partition(':')
        site = site.strip()
        fatal = False
        mode, value = None, None
        for part in rest.split(','):
            part = part.strip()
            if not part:
                continue
            if part == 'always':
                mode, value = 'always', None
            elif part.startswith('kind='):
                kind = part[5:]
                if kind not in ('transient', 'fatal'):
                    raise ValueError(
                        "PADDLE_FAULT_SPEC site %r: unknown kind=%r "
                        "(transient|fatal)" % (site, kind))
                fatal = kind == 'fatal'
            elif '=' in part:
                k, _, v = part.partition('=')
                if k not in ('p', 'nth', 'n', 'every'):
                    raise ValueError(
                        "PADDLE_FAULT_SPEC site %r: unknown trigger %r "
                        "(always|p=|nth=|n=|every=)" % (site, k))
                try:
                    mode, value = k, float(v)
                except ValueError:
                    raise ValueError(
                        "PADDLE_FAULT_SPEC site %r: non-numeric trigger "
                        "value %r" % (site, v))
                if k != 'p' and value < 1:
                    raise ValueError(
                        "PADDLE_FAULT_SPEC site %r: %s=%s must be >= 1"
                        % (site, k, v))
            else:
                raise ValueError(
                    "PADDLE_FAULT_SPEC site %r: unparseable part %r"
                    % (site, part))
        if mode is None:
            raise ValueError(
                "PADDLE_FAULT_SPEC site %r: no trigger (always|p=|nth=|"
                "n=|every=)" % site)
        rules[site] = _FaultRule(site, mode, value, fatal)
    return rules


_fault_lock = threading.Lock()
_env_rules = (None, {})         # (spec string it was parsed from, rules)
_prog_rules = {}                # install_fault() registrations (tests)


def maybe_fault(site):
    """Raise an InjectedFault at `site` if the active fault spec says so.
    The no-fault fast path is one env read + a falsy check — cheap enough
    for the executor hot path."""
    global _env_rules
    spec = os.environ.get('PADDLE_FAULT_SPEC', '')
    if not spec and not _prog_rules:
        return
    with _fault_lock:
        rule = _prog_rules.get(site)
        if rule is None and spec:
            if _env_rules[0] != spec:
                # counters survive only within one spec string; a changed
                # spec is a new fault schedule
                _env_rules = (spec, _parse_spec(spec))
            rule = _env_rules[1].get(site)
        if rule is None or not rule.fire():
            return
        transient = not rule.fatal
    monitor.inc('fault_injected_total', labels={'site': site})
    raise InjectedFault(
        site, "injected fault at %r (call %d of spec %r)%s"
        % (site, rule.calls, spec or '<install_fault>',
           '' if transient else ' [fatal]'),
        transient=transient)


def install_fault(site, mode='always', value=None, fatal=False):
    """Programmatic fault registration (tests): overrides any
    PADDLE_FAULT_SPEC clause for `site`."""
    with _fault_lock:
        _prog_rules[site] = _FaultRule(site, mode, value, fatal)


def clear_faults():
    """Drop programmatic registrations and the parsed-env cache."""
    global _env_rules
    with _fault_lock:
        _prog_rules.clear()
        _env_rules = (None, {})


class fault_spec(object):
    """Context manager scoping a PADDLE_FAULT_SPEC string to a block::

        with resilience.fault_spec('ckpt_write:always'):
            ...
    """

    def __init__(self, spec):
        self._spec = spec
        self._prev = None

    def __enter__(self):
        self._prev = os.environ.get('PADDLE_FAULT_SPEC')
        os.environ['PADDLE_FAULT_SPEC'] = self._spec
        return self

    def __exit__(self, *exc):
        if self._prev is None:
            os.environ.pop('PADDLE_FAULT_SPEC', None)
        else:
            os.environ['PADDLE_FAULT_SPEC'] = self._prev
        clear_faults()
        return False


# ---------------------------------------------------------------------------
# transient-error classification + retry policy


# substrings marking an error worth retrying: the XLA/gRPC status codes a
# transient infrastructure failure surfaces as (TF's retry classes), plus
# socket-level connect noise from the coordinator paths
_TRANSIENT_MARKERS = (
    'RESOURCE_EXHAUSTED', 'UNAVAILABLE', 'DEADLINE_EXCEEDED', 'ABORTED',
    'CANCELLED', 'connection reset', 'connection refused', 'broken pipe',
    'socket closed', 'failed to connect', 'transient',
)


def is_transient(exc):
    """Is `exc` worth retrying? InjectedFault carries its own flag;
    connection-level OSErrors and status-code-bearing messages match the
    marker list; everything else (shape errors, user bugs) is permanent."""
    if isinstance(exc, InjectedFault):
        return exc.transient
    if isinstance(exc, (ConnectionError, TimeoutError)):
        return True
    msg = str(exc).lower()
    return any(m.lower() in msg for m in _TRANSIENT_MARKERS)


def _env_float(name, default):
    try:
        return float(os.environ.get(name, '') or default)
    except ValueError:
        return default


class RetryPolicy(object):
    """Exponential backoff with full jitter and a wall-clock deadline.

    max_attempts counts TOTAL tries (first + retries). Delay before retry
    k (1-based) is ``min(max_delay, base * multiplier**(k-1))`` scaled by
    a uniform jitter in [1-jitter, 1+jitter]; the deadline bounds the sum
    of sleeps so a retry loop can never outlive its caller's patience.
    Defaults come from PADDLE_RETRY_* env vars at construction time."""

    def __init__(self, max_attempts=None, base_delay_s=None, max_delay_s=None,
                 multiplier=2.0, jitter=0.25, deadline_s=None):
        self.max_attempts = int(max_attempts if max_attempts is not None
                                else _env_float('PADDLE_RETRY_MAX_ATTEMPTS', 4))
        self.base_delay_s = (base_delay_s if base_delay_s is not None
                             else _env_float('PADDLE_RETRY_BASE_S', 0.05))
        self.max_delay_s = (max_delay_s if max_delay_s is not None
                            else _env_float('PADDLE_RETRY_MAX_S', 2.0))
        self.multiplier = float(multiplier)
        self.jitter = float(jitter)
        self.deadline_s = (deadline_s if deadline_s is not None
                           else _env_float('PADDLE_RETRY_DEADLINE_S', 30.0))
        # shared jittered stream; seeded RNG keeps schedules reproducible
        # under PADDLE_FAULT_SEED without touching global random state
        seed = os.environ.get('PADDLE_FAULT_SEED')
        self._rng = random.Random(int(seed)) if seed else random.Random()

    def delay(self, attempt):
        """Backoff before retry `attempt` (1-based), jittered."""
        d = min(self.max_delay_s,
                self.base_delay_s * (self.multiplier ** (attempt - 1)))
        if self.jitter:
            d *= 1.0 + self.jitter * (2.0 * self._rng.random() - 1.0)
        return max(0.0, d)

    def call(self, fn, site='generic', retryable=None, state=None):
        """Run fn(); on a transient error, back off and re-invoke until
        success, a permanent error, attempt exhaustion, or the deadline.
        See retry_after for `state` (donated-buffer guard)."""
        try:
            return fn()
        except Exception as e:          # noqa: BLE001 — classified below
            return self.resume(e, fn, site=site, retryable=retryable,
                               state=state)

    def resume(self, exc, fn, site='generic', retryable=None, state=None):
        """The except-block half of call(): given an already-raised `exc`,
        retry fn() under this policy. Re-raises `exc` unchanged when it is
        not retryable — the zero-overhead pattern for hot paths that only
        pay for retry logic once something actually failed."""
        check = retryable if retryable is not None else is_transient
        if not check(exc):
            raise exc

        def _donated_giveup(cause):
            monitor.inc('retry_giveup_total', labels={'site': site})
            trace_mod.note('retry_giveup', site=site, reason='donated',
                           error=type(cause).__name__)
            blackbox.record('retry_giveup', error=cause, site=site,
                            reason='donated')
            return RuntimeError(
                "cannot retry %r after %s: the failed attempt consumed "
                "donated input buffers (set PADDLE_DONATE=0 to trade peak "
                "memory for retryability of mid-run faults)"
                % (site, type(cause).__name__))

        if state is not None and not _buffers_alive(state):
            raise _donated_giveup(exc) from exc
        t0 = time.monotonic()
        last = exc
        for attempt in range(1, self.max_attempts):
            d = self.delay(attempt)
            if time.monotonic() + d - t0 > self.deadline_s:
                break
            monitor.inc('retry_attempt_total', labels={'site': site})
            # the backoff sleep is dead wall the device sits idle for —
            # the goodput layer's 'retry_backoff' loss bucket reads this
            # histogram's sum (docs/observability.md)
            monitor.observe('retry_backoff_seconds', d,
                            labels={'site': site})
            with monitor.span('retry_backoff:%s' % site):
                time.sleep(d)
            try:
                return fn()
            except Exception as e:      # noqa: BLE001 — classified below
                last = e
                if not check(e):
                    raise
                if state is not None and not _buffers_alive(state):
                    # name the real blocker, not the last transient error
                    raise _donated_giveup(e) from e
        monitor.inc('retry_giveup_total', labels={'site': site})
        trace_mod.note('retry_giveup', site=site, reason='exhausted',
                       error=type(last).__name__)
        blackbox.record('retry_giveup', error=last, site=site,
                        reason='exhausted', attempts=self.max_attempts)
        raise last


def _buffers_alive(state):
    """False if any value in `state` is a donated (deleted) jax buffer —
    re-invoking a compiled fn with consumed inputs would only mask the
    original error with jax's opaque deleted-buffer message."""
    for v in state.values():
        d = getattr(v, 'is_deleted', None)
        if callable(d):
            try:
                if d():
                    return False
            except Exception:
                return False
    return True


def retry_call(fn, site='generic', policy=None, retryable=None, state=None):
    """Run fn() under `policy` (default: env-configured RetryPolicy)."""
    return (policy or RetryPolicy()).call(fn, site=site, retryable=retryable,
                                          state=state)


def retry_after(exc, fn, site='generic', policy=None, retryable=None,
                state=None):
    """Except-block entry point: re-raise `exc` if permanent, else retry
    fn() with backoff. Keeps the success path of hot callers completely
    free of retry machinery."""
    return (policy or RetryPolicy()).resume(exc, fn, site=site,
                                            retryable=retryable, state=state)


# ---------------------------------------------------------------------------
# checkpoint hardening primitives (used by checkpoint.py / io.py)


MANIFEST_NAME = 'paddle_manifest.json'


def array_crc32(arr):
    """Stable content digest of one tensor: crc32 over dtype/shape header +
    raw bytes (C order). Cheap enough to run at every checkpoint write."""
    arr = np.ascontiguousarray(arr)
    head = ('%s|%s|' % (arr.dtype.str, arr.shape)).encode()
    return zlib.crc32(arr.tobytes(), zlib.crc32(head)) & 0xFFFFFFFF


def build_manifest(state, step=None, extra=None):
    """Manifest dict for a state pytree: per-tensor shape/dtype/crc32.
    Values that are not fully host-readable (multi-host sharded arrays)
    record crc32=None — present-and-well-formed is still checked.

    Cost note: crc computation pulls every tensor host-side AGAIN (orbax
    already did one D2H to serialize) and crc32s all bytes (~1 GB/s).
    Fine for small/medium state; for multi-GB state where the doubled
    host traffic matters, ``PADDLE_CKPT_CRC=0`` keeps the structural
    manifest (names/shapes/dtypes verified at restore) without crcs."""
    want_crc = os.environ.get('PADDLE_CKPT_CRC', '1') != '0'
    tensors = {}
    for name, v in state.items():
        ent = {'crc32': None, 'shape': None, 'dtype': None}
        try:
            if getattr(v, 'is_fully_addressable', True):
                if want_crc:
                    arr = np.asarray(v)
                    ent = {'crc32': array_crc32(arr),
                           'shape': list(arr.shape),
                           'dtype': str(arr.dtype)}
                else:
                    # metadata without the D2H copy; python scalars
                    # (no .shape/.dtype) go through tiny np.asarray
                    if hasattr(v, 'shape') and hasattr(v, 'dtype'):
                        ent = {'crc32': None, 'shape': list(v.shape),
                               'dtype': str(v.dtype)}
                    else:
                        arr = np.asarray(v)
                        ent = {'crc32': None, 'shape': list(arr.shape),
                               'dtype': str(arr.dtype)}
        except Exception:
            pass                        # unreadable value: structural only
        tensors[name] = ent
    out = {'format': 'paddle_tpu_ckpt', 'version': 1, 'step': step,
           'tensors': tensors}
    if extra:
        out.update(extra)
    return out


def verify_manifest(manifest, restored):
    """Names whose restored bytes do not match the manifest (missing,
    shape/dtype drift, or crc mismatch). Empty list == valid."""
    bad = []
    for name, ent in manifest.get('tensors', {}).items():
        if name not in restored:
            bad.append(name)
            continue
        if ent.get('shape') is None:
            continue                    # recorded as unverifiable at save
        arr = np.asarray(restored[name])
        if (list(arr.shape) != ent.get('shape')
                or str(arr.dtype) != ent.get('dtype')):
            bad.append(name)
        elif ent.get('crc32') is not None and \
                array_crc32(arr) != ent['crc32']:
            bad.append(name)
    return bad


def fsync_dir(path):
    """fsync a DIRECTORY so a rename into it survives power loss; no-op on
    filesystems/platforms without directory fds."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def atomic_write_bytes(path, data):
    """tmp + fsync + rename publication of one file: readers observe the
    old content or the new content, never a torn write. The ckpt_write
    fault site fires BETWEEN write and publish — the worst crash point —
    and the tmp file is always cleaned up."""
    tmp = path + '.tmp.%d' % os.getpid()
    try:
        with open(tmp, 'wb') as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
        maybe_fault('ckpt_write')
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    fsync_dir(os.path.dirname(os.path.abspath(path)))


def pid_alive(pid):
    """Best-effort liveness probe shared by the tmp-sweep paths (here and
    checkpoint._clean_stale_tmp): EPERM counts as alive."""
    if pid is None:
        return False
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False
    except OSError:
        return True


def sweep_stale_tmp_files(dirname):
    """Remove '*.tmp.<pid>[.npy|.npz]' leftovers from crashed
    atomic_file/atomic_write_bytes writers — without a sweep they
    accumulate full-size partial files across every crash of a
    long-lived job until the save directory hits ENOSPC. A file is
    swept only when its writer pid is gone AND it is older than
    PADDLE_CKPT_TMP_TTL_S (default 1 h): pid liveness is host-local, so
    on shared storage another HOST's in-flight write looks pid-dead —
    the age guard is what actually protects it (an atomic publish window
    is seconds; leftovers age indefinitely)."""
    try:
        names = os.listdir(dirname)
    except OSError:
        return
    ttl = _env_float('PADDLE_CKPT_TMP_TTL_S', 3600.0)
    for n in names:
        if '.tmp.' not in n:
            continue
        pid_part = n.split('.tmp.', 1)[1].split('.', 1)[0]
        if not pid_part.isdigit() or pid_alive(int(pid_part)):
            continue
        path = os.path.join(dirname, n)
        try:
            if not os.path.isfile(path) or \
                    time.time() - os.path.getmtime(path) < ttl:
                continue
            os.unlink(path)
        except OSError:
            pass


class atomic_file(object):
    """Context manager for tmp+fsync+rename file publication::

        with resilience.atomic_file(path) as tmp:
            np.savez(tmp, **arrays)

    The body writes to `tmp`; on success the file is fsynced, the
    ``ckpt_write`` fault site is checked, and the tmp is renamed over
    `path` (readers never observe a torn file). On failure the tmp is
    removed and nothing is published."""

    def __init__(self, path):
        self._path = path
        self._tmp = path + '.tmp.%d' % os.getpid()

    def __enter__(self):
        return self._tmp

    def _resolve_tmp(self):
        # np.save/np.savez append .npy/.npz when missing — accept either
        # the exact tmp name or the extended one
        if not os.path.exists(self._tmp):
            for ext in ('.npy', '.npz'):
                if os.path.exists(self._tmp + ext):
                    return self._tmp + ext
        return self._tmp

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            # the body may have written the EXTENDED name before failing
            # (np.savez mid-write ENOSPC) — remove whichever exists
            try:
                os.unlink(self._resolve_tmp())
            except OSError:
                pass
            return False
        tmp = self._resolve_tmp()
        try:
            with open(tmp, 'rb') as f:
                os.fsync(f.fileno())
            maybe_fault('ckpt_write')
            os.replace(tmp, self._path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        fsync_dir(os.path.dirname(os.path.abspath(self._path)))
        return False


def write_manifest(dirname, manifest):
    import json
    atomic_write_bytes(os.path.join(dirname, MANIFEST_NAME),
                       json.dumps(manifest, sort_keys=True).encode())


def read_manifest(dirname):
    """Manifest dict, or None when absent/unreadable (pre-hardening
    checkpoints stay loadable; they just can't be crc-verified)."""
    import json
    path = os.path.join(dirname, MANIFEST_NAME)
    try:
        with open(path, 'rb') as f:
            return json.loads(f.read().decode())
    except (OSError, ValueError):
        return None


# ---------------------------------------------------------------------------
# non-finite-step recovery


def _finite(value):
    arr = np.asarray(value)
    return arr.dtype.kind != 'f' or bool(np.isfinite(arr).all())


class TrainingGuard(object):
    """Step wrapper that survives non-finite losses.

    ::

        guard = resilience.TrainingGuard(exe, main_prog, loss_name=loss.name,
                                         scope=scope, max_bad_steps=3)
        for batch in data:
            fetches = guard.step(feed=batch, fetch_list=[loss])
            if guard.last_step_skipped:
                continue            # optimizer update was rolled back

    Before each step the guard snapshots (by reference) every persistable
    the program writes; if the fetched loss — or any float fetch, or, with
    ``check_state=True``, any written state entry — comes back non-finite,
    the scope is rolled back to the snapshot (bit-identical: the old device
    buffers are simply re-bound), ``nonfinite_skip_total`` is incremented,
    and an optional loss-scale scalar (``loss_scale_name``) is multiplied
    by ``backoff_factor``. After ``max_bad_steps`` CONSECUTIVE bad steps it
    raises NonFiniteError — at that point the data or the model is broken
    and silently spinning would hide it. A finite step resets the streak
    and, when ``growth_interval`` > 0, doubles the loss scale every that
    many good steps (bounded by ``max_loss_scale``).

    Guarded runs force buffer donation OFF for that one call (the
    executor's per-call ``donate=False`` override — no process-global env
    flipping, so concurrent unguarded runs on other threads keep their own
    donation behavior) so the pre-step snapshot stays alive for rollback;
    peak state memory is 2x during the step — the standard cost of any
    rollback-capable trainer. The guard composes with
    FLAGS_check_nan_inf: the executor's NaN raise is caught and treated
    as a bad step (the scope rebind happens before that raise, so the
    rollback still sees live buffers).
    """

    def __init__(self, executor, program, loss_name=None, scope=None,
                 max_bad_steps=3, loss_scale_name=None, backoff_factor=0.5,
                 growth_interval=0, growth_factor=2.0,
                 max_loss_scale=2.0 ** 15, check_state=False, health=None):
        if max_bad_steps < 1:
            raise ValueError("max_bad_steps must be >= 1")
        self._exe = executor
        self._program = program
        self._loss_name = loss_name
        self._scope = scope
        # training-health observatory (health.py). None (default): follow
        # PADDLE_HEALTH. True/'watch': telemetry only — per-layer stats
        # ride the step fetch, detectors trip counters/bundles. 'preempt':
        # additionally roll the step back on a confirmed grad_explosion /
        # loss_spike BEFORE anything goes non-finite (same snapshot/
        # rollback + loss-scale backoff as the NaN path). False: off.
        from . import health as _health_mod
        mode = health
        if mode is None:
            mode = 'watch' if _health_mod.enabled() else False
        elif mode is True:
            mode = 'watch'
        if mode not in (False, 'watch', 'preempt'):
            raise ValueError("health must be one of None/True/False/"
                             "'watch'/'preempt', got %r" % (health,))
        self.health_mode = mode or None
        if self.health_mode:
            _health_mod.instrument(
                getattr(program, '_program', program), loss_name)
        self.max_bad_steps = int(max_bad_steps)
        self.loss_scale_name = loss_scale_name
        self.backoff_factor = float(backoff_factor)
        self.growth_interval = int(growth_interval)
        self.growth_factor = float(growth_factor)
        self.max_loss_scale = float(max_loss_scale)
        self.check_state = bool(check_state)
        self.bad_steps = 0              # consecutive
        self.total_skipped = 0
        self.last_step_skipped = False
        # PADDLE_NAN_LOCALIZE=1: info dict of the op the last bad step's
        # non-finite value was localized to (analysis.localize_nonfinite),
        # None when localization is off / found nothing / step was good
        self.last_localization = None
        self._good_streak = 0
        self._written_cache = None      # (program version, names)

    def _written_names(self):
        cached = self._written_cache
        if cached is not None and cached[0] == self._program._version:
            return cached[1]
        from .core import lowering
        _, written = lowering.analyze_state(self._program, [])
        names = sorted(written)
        self._written_cache = (self._program._version, names)
        return names

    def _scale_adjust(self, scope, factor):
        if not self.loss_scale_name or not scope.has(self.loss_scale_name):
            return
        cur = np.asarray(scope.get(self.loss_scale_name))
        new = np.minimum(cur * factor, self.max_loss_scale).astype(cur.dtype)
        scope.set(self.loss_scale_name, new)

    # -- shared snapshot/restore (NaN path AND preemptive health path) ----
    def _snapshot(self, scope):
        """By-reference snapshot of every written persistable, the lod
        table, and the program's RNG run counter — everything a rollback
        must restore."""
        prog = getattr(self._program, '_program', self._program)
        state = {}
        for n in self._written_names():
            if scope.has(n):
                state[n] = scope.get(n)
        return {'state': state,
                'lods': dict(getattr(scope, '_lods', {})),
                'rng': int(getattr(prog, '_rng_run_counter', 0) or 0)}

    def _restore(self, scope, snap):
        """Roll the scope back to a _snapshot and REWIND the RNG run
        counter (the checkpoint-restore rewind rule): the retried step
        replays the same dropout stream the rolled-back step consumed,
        so a guarded trajectory with a skipped step is bit-identical to
        an unguarded one over the same good batches. The failed step's
        own key stays on program._last_run_key for NaN localization."""
        scope.update(snap['state'])
        scope._lods = snap['lods']
        # drop state the bad step CREATED (not present pre-step): a
        # half-written first step must not survive the rollback
        for n in self._written_names():
            if n not in snap['state'] and scope.has(n):
                scope.drop(n)
        prog = getattr(self._program, '_program', self._program)
        prog._rng_run_counter = snap['rng']

    def stats(self):
        """Loop-surface stats block; ['health'] carries the observatory
        view when health mode is on (None otherwise)."""
        out = {'bad_steps': self.bad_steps,
               'total_skipped': self.total_skipped,
               'last_step_skipped': self.last_step_skipped,
               'health_mode': self.health_mode,
               'health': None}
        if self.health_mode:
            from . import health as _health_mod
            out['health'] = _health_mod.stats(
                getattr(self._program, '_program', self._program))
        return out

    def step(self, feed=None, fetch_list=None, **run_kw):
        """One guarded executor run; returns the fetches of the requested
        fetch_list (loss is fetched internally when not already listed).
        On a skipped step the returned fetches are the BAD values (for
        logging) and the scope holds the rolled-back state."""
        from .executor import global_scope
        scope = self._scope if self._scope is not None else global_scope()
        fetch_list = list(fetch_list or [])
        names = [v if isinstance(v, str) else v.name for v in fetch_list]
        extra_loss = (self._loss_name is not None
                      and self._loss_name not in names)
        run_fetch = fetch_list + ([self._loss_name] if extra_loss else [])
        health_fetch = None
        if self.health_mode:
            from . import health as _health_mod
            hf = _health_mod.fetch_name(
                getattr(self._program, '_program', self._program))
            if hf and hf not in names:
                health_fetch = hf
                run_fetch = run_fetch + [hf]

        snap = self._snapshot(scope)

        bad = False
        raised = False
        run_localization = None     # executor-side provenance, if it ran
        fetches = []
        # donation off for THIS call only (the rollback snapshot must
        # outlive the run) via the executor's per-call override — runs on
        # other threads, guarded or not, are untouched
        run_kw.setdefault('donate', False)
        try:
            fetches = self._exe.run(self._program, feed=feed,
                                    fetch_list=run_fetch, scope=scope,
                                    **run_kw)
        except (RuntimeError, FloatingPointError) as e:
            # FLAGS_check_nan_inf / jax debug_nans surface the bad
            # step as a raise; anything else propagates untouched
            if not isinstance(e, FloatingPointError) and \
                    'NaN/Inf' not in str(e):
                raise
            bad = True
            raised = True
            run_localization = getattr(e, 'nonfinite_localization', None)
            # the raise swallowed the fetch values; keep the
            # documented "bad values for logging" return shape with
            # NaN stand-ins so `guard.step(...)[0]` survives the
            # step it exists to survive. 1-element ARRAYS, not 0-d
            # scalars: scalar-loss fetches are shaped arrays on the
            # normal path, and `out[0][0]`-style logging must not
            # die on exactly the step the guard exists to survive
            fetches = [np.full((1,), np.nan, np.float32)
                       for _ in run_fetch]

        if not bad:
            check_vals = list(fetches)
            bad = not all(_finite(v) for v in check_vals)
            if not bad and self.check_state:
                bad = not all(
                    _finite(scope.get(n)) for n in self._written_names()
                    if scope.has(n))

        # health observatory: decode the stat vector the step already
        # fetched (skip the raise path — its fetches are NaN stand-ins,
        # not real values) and collect the detector verdicts
        detected = ()
        preempt = False
        if health_fetch and not raised and fetches:
            from . import health as _health_mod
            detected = _health_mod.observe(
                getattr(self._program, '_program', self._program),
                fetches[-1])
            if not bad and self.health_mode == 'preempt' and \
                    any(k in _health_mod.PREEMPT_KINDS for k in detected):
                # confirmed divergence while everything is still finite:
                # roll back NOW, before the NaN destroys the evidence
                preempt = True
                monitor.inc('health_preempt_rollback_total')

        if bad or preempt:
            self._restore(scope, snap)
            # opt-in NaN provenance (PADDLE_NAN_LOCALIZE=1): reuse the
            # localization the executor's check_nan_inf path already paid
            # for when it raised; otherwise replay the failed step against
            # the just-restored pre-step state, with the SAME rng key, and
            # record which op went non-finite first
            if preempt:
                # nothing is non-finite yet — there is no NaN to localize
                self.last_localization = None
            elif run_localization is not None:
                self.last_localization = run_localization
            else:
                from . import analysis
                prog = getattr(self._program, '_program', self._program)
                self.last_localization = analysis.localize_from_scope(
                    self._exe, prog, feed, scope,
                    getattr(prog, '_last_run_key', None))
            self._scale_adjust(scope, self.backoff_factor)
            self.bad_steps += 1
            self.total_skipped += 1
            self._good_streak = 0
            self.last_step_skipped = True
            if not preempt:
                monitor.inc('nonfinite_skip_total')
            if self.bad_steps >= self.max_bad_steps:
                monitor.inc('nonfinite_escalate_total')
                from . import analysis
                where = ''
                if self.last_localization:
                    where = '; ' + analysis.format_localization(
                        self.last_localization)
                if blackbox.enabled():
                    # the replayable incident: the scope already holds the
                    # rolled-back PRE-step state and the program still has
                    # the failed step's rng key — exactly what
                    # localize_from_scope (and tools/blackbox.py replay)
                    # re-executes. With the health observatory on, the
                    # bundle also embeds the per-layer stat history.
                    prog = getattr(self._program, '_program', self._program)
                    extra = {}
                    if self.health_mode:
                        from . import health as _health_mod
                        extra['health'] = _health_mod.stats(prog)
                    blackbox.record(
                        'nonfinite_escalate', program=prog, feed=feed,
                        state={n: scope.get(n) for n in scope.names()},
                        lods=dict(getattr(scope, '_lods', {})),
                        key_arr=getattr(prog, '_last_run_key', None),
                        localization=self.last_localization,
                        bad_steps=self.bad_steps,
                        loss=self._loss_name, **extra)
                raise NonFiniteError(
                    "TrainingGuard: %d consecutive %s steps "
                    "(loss %r) — the optimizer update was skipped each "
                    "time; inspect the data pipeline / lower the learning "
                    "rate / check loss scaling%s"
                    % (self.bad_steps,
                       'non-finite' if not preempt
                       else 'diverging (health-preempted)',
                       self._loss_name or '<unnamed>', where))
        else:
            self.bad_steps = 0
            self.last_step_skipped = False
            self.last_localization = None
            self._good_streak += 1
            if self.growth_interval and \
                    self._good_streak % self.growth_interval == 0:
                self._scale_adjust(scope, self.growth_factor)

        if extra_loss or health_fetch:
            return fetches[:len(fetch_list)]
        return fetches


# ---------------------------------------------------------------------------
# preemption-aware (elastic) training


def elastic_train_loop(step_fn, manager, num_steps, start_step=0, mesh=None,
                       devices_fn=None, reshard=None, max_resumes=3,
                       on_resume=None):
    """Run ``step_fn(step, mesh)`` for ``num_steps`` steps, checkpointing
    through `manager` (a ``checkpoint.CheckpointManager``) — and SURVIVE
    preemptions: a ``WorkerFailedError`` (dead rank), a ``NonFiniteError``
    (TrainingGuard escalation) or a fatal ``InjectedFault`` (the chaos
    drill's stand-in for a mid-step kill) escaping a step triggers an
    elastic resume instead of a crash:

    1. the surviving device set is re-read (``devices_fn()``, default
       ``jax.devices()``),
    2. a mesh with the same axis structure is rebuilt over it
       (``parallel.mesh.surviving_mesh`` — 'data' shrinks or grows, other
       axes keep their degree; no prior mesh means a fresh data mesh),
    3. the newest valid checkpoint is restored **resharded onto that
       mesh** (``manager.restore_latest(mesh=...)`` — corrupt/partial
       checkpoints are skipped, injected ``ckpt_restore`` faults
       included), and
    4. the loop replays from the checkpointed step.

    GROW-BACK: the loop also probes ``devices_fn`` each step in the
    other direction — when it reports MORE devices than the current mesh
    uses (preempted capacity returned), the just-completed step is
    force-published (checkpoint-publish barrier, async writer flushed),
    restored resharded onto the larger mesh, and training continues at
    the NEXT step: no replay, bitwise vs an uninterrupted run.
    ``elastic_grow_total`` + ``elastic_resume_total`` count it,
    ``ckpt_reshard_total{direction=grow}`` stamps the reshard, and
    ``on_resume(step, mesh, None)`` announces it — a ``None`` exception
    distinguishes growth from failure resumes.

    Cadenced saves run under the ``ckpt_write`` retry policy; a save that
    still fails only warns (``elastic_save_skipped_total``) — a broken
    checkpoint disk degrades the recovery point, it does not stop
    training. Transient faults never reach this loop (the executor's
    retry layer absorbs them); one that does means retries were
    exhausted — a worker-grade failure. After ``max_resumes`` resumes
    WITHOUT forward progress the error propagates (completing a step at
    or past the failure point resets the budget, so sparse preemptions
    over a long job never exhaust it): at that point the fleet is dying
    faster than it can recover and an operator should look. A failure before the first checkpoint exists is
    re-raised with that diagnosis rather than silently restarting from
    scratch.

    Returns the list of per-step ``step_fn`` outputs (length
    ``num_steps``); replayed steps overwrite their first attempt, so the
    result reads as one uninterrupted trajectory. Each resume increments
    ``elastic_resume_total`` and updates the ``elastic_world_size``
    gauge; ``on_resume(step, mesh, exc)`` is called before the first
    replayed step.

    The whole run is one trace (kind ``elastic``, always kept): every
    resume, replicate-fallback, save-skip, and give-up lands in the
    trace log as a structured event stamped with the incarnation's
    trace ID — a post-mortem reconstructs the full recovery sequence
    (who died, which direction the reshard went, what world size came
    back) from one ``tools/tracereport.py`` read. See
    docs/observability.md."""
    from .distributed.launch import WorkerFailedError
    from .parallel import mesh as mesh_mod

    tr = trace_mod.start('elastic', name='elastic_train_loop',
                         sampled=True)
    with trace_mod.activate(tr):
        try:
            outputs = _elastic_loop_body(
                step_fn, manager, num_steps, start_step, mesh, devices_fn,
                reshard, max_resumes, on_resume, tr, WorkerFailedError,
                mesh_mod)
        except BaseException as e:
            tr.finish('error', error=e)
            raise
    tr.finish('ok', steps=int(num_steps))
    return outputs


def _elastic_loop_body(step_fn, manager, num_steps, start_step, mesh,
                       devices_fn, reshard, max_resumes, on_resume, tr,
                       WorkerFailedError, mesh_mod):
    outputs = [None] * int(num_steps)
    step = int(start_step)
    resumes = 0
    fail_step = None        # step of the last failure; progress past it
    # resets the resume budget — max_resumes bounds failures WITHOUT
    # forward progress, not lifetime preemptions of a month-long job
    while step < num_steps:
        if devices_fn is not None and mesh is not None and \
                step > int(start_step):
            # GROW-BACK probe: preempted capacity that returned mid-run
            # re-expands the job instead of limping shrunken to the end.
            # devices_fn() reporting more devices than the mesh uses
            # triggers a checkpoint-publish barrier (force-save the
            # just-completed step, flush any async publish), a reshard
            # of that checkpoint onto the larger mesh, and a resume at
            # the NEXT step — no step replays and no state is
            # approximated, so the trajectory stays bitwise vs an
            # uninterrupted run.
            devices = list(devices_fn())
            if len(devices) > int(mesh.devices.size):
                grown = mesh_mod.surviving_mesh(mesh, devices)
                if int(grown.devices.size) > int(mesh.devices.size):
                    t_grow = time.perf_counter()
                    old_size = int(mesh.devices.size)
                    manager.save(step - 1, force=True)
                    flush = getattr(manager, 'flush', None)
                    if callable(flush):
                        flush()
                    rstep, _path, _names = manager.restore_latest(
                        mesh=grown, reshard=reshard)
                    mesh = grown
                    if rstep is not None:
                        step = rstep + 1
                    new_size = int(mesh.devices.size)
                    monitor.inc('elastic_resume_total')
                    monitor.inc('elastic_grow_total')
                    monitor.set_gauge('elastic_world_size',
                                      float(new_size))
                    tr.event('elastic_grow', step=step,
                             world_size=new_size, old_world_size=old_size,
                             restored_step=rstep)
                    blackbox.record('elastic_grow', step=step,
                                    world_size=new_size,
                                    old_world_size=old_size,
                                    restored_step=rstep)
                    if on_resume is not None:
                        on_resume(step, mesh, None)
                    monitor.observe('elastic_recovery_seconds',
                                    time.perf_counter() - t_grow)
        try:
            out = step_fn(step, mesh)
        except (WorkerFailedError, NonFiniteError, InjectedFault) as e:
            t_recover = time.perf_counter()
            resumes += 1
            if resumes > max_resumes:
                monitor.inc('elastic_giveup_total')
                tr.event('elastic_giveup', step=step, resumes=resumes,
                         failure=type(e).__name__)
                blackbox.record('elastic_giveup', error=e, step=step,
                                resumes=resumes)
                raise
            fail_step = step
            import jax
            devices = list(devices_fn()) if devices_fn is not None \
                else list(jax.devices())
            old_size = int(mesh.devices.size) if mesh is not None else None
            if mesh is not None:
                mesh = mesh_mod.surviving_mesh(mesh, devices)
            else:
                mesh = mesh_mod.data_mesh(devices=devices)
            new_size = int(mesh.devices.size)
            if old_size is None:
                direction = 'fresh'
            elif new_size == old_size:
                direction = 'same'
            else:
                direction = 'shrink' if new_size < old_size else 'grow'
            try:
                rstep, path, _names = manager.restore_latest(
                    mesh=mesh, reshard=reshard)
            except IOError as restore_err:
                if manager.latest_step() is None:
                    raise RuntimeError(
                        "elastic_train_loop: step %d failed (%s: %s) "
                        "before any restorable checkpoint existed under "
                        "%r — save at least one checkpoint "
                        "(manager.save(step, force=True) after init) to "
                        "make the job preemption-safe"
                        % (step, type(e).__name__, e, manager.dirname)
                    ) from restore_err
                if reshard is None:
                    # checkpoints EXIST but none restored onto the
                    # rebuilt mesh — possibly a divisibility failure
                    # (e.g. 8 devices shrank to 5 and a dim sharded over
                    # 'data' no longer divides), which full replication
                    # always survives; a replicated resume beats a dead
                    # job, and the spec-mapped layout returns at the next
                    # save/restore on a divisible fleet.
                    import warnings
                    warnings.warn(
                        "elastic_train_loop: no checkpoint restored onto "
                        "the rebuilt mesh with its saved specs (%s); "
                        "retrying fully replicated" % restore_err,
                        stacklevel=2)
                    monitor.inc('elastic_replicate_fallback_total')
                    tr.event('elastic_replicate_fallback', step=step,
                             world_size=new_size)
                    try:
                        rstep, path, _names = manager.restore_latest(
                            mesh=mesh, reshard='replicate')
                    except IOError as rep_err:
                        # replication failing too means the checkpoints
                        # themselves are bad (corruption), not the mesh
                        raise RuntimeError(
                            "elastic_train_loop: checkpoints exist under "
                            "%r but none restored even fully replicated "
                            "— they are corrupt/unreadable, not merely "
                            "indivisible (%s)"
                            % (manager.dirname, rep_err)) from rep_err
                else:
                    raise RuntimeError(
                        "elastic_train_loop: checkpoints exist under %r "
                        "but none restored onto the rebuilt mesh (%s)"
                        % (manager.dirname, restore_err)) from restore_err
            if rstep is not None and rstep >= step:
                # this loop only checkpoints COMPLETED steps, so a
                # restored step at or past the one that just failed can
                # only come from some other run's leftovers — resuming
                # "past the end" would silently return a trajectory with
                # holes
                raise RuntimeError(
                    "elastic_train_loop: restored checkpoint step_%d from "
                    "%r is not from this run (the failure was at step %d) "
                    "— the checkpoint dir holds a newer/foreign run; "
                    "point the CheckpointManager at a fresh directory"
                    % (rstep, manager.dirname, step))
            step = (rstep + 1) if rstep is not None else int(start_step)
            monitor.inc('elastic_resume_total')
            monitor.set_gauge('elastic_world_size',
                              float(mesh.devices.size))
            tr.event('elastic_resume', step=fail_step,
                     failure=type(e).__name__, world_size=new_size,
                     reshard_direction=direction, restored_step=rstep,
                     resume_step=step)
            if on_resume is not None:
                on_resume(step, mesh, e)
            # failure -> restored-and-ready wall: the 'elastic_recovery'
            # goodput loss bucket (the restore itself also counts into
            # ckpt_restore_seconds; recovery covers mesh rebuild + both)
            monitor.observe('elastic_recovery_seconds',
                            time.perf_counter() - t_recover)
            blackbox.record('elastic_resume', error=e, step=fail_step,
                            world_size=new_size,
                            reshard_direction=direction,
                            restored_step=rstep, resume_step=step)
            continue
        outputs[step] = out
        if fail_step is not None and step >= fail_step:
            resumes = 0         # replay caught up past the failure point
            fail_step = None
        try:
            retry_call(lambda: manager.save(step), site='ckpt_write')
        except Exception as save_err:   # noqa: BLE001 — degrade, don't die
            # a failed SAVE is not a preemption: training continues, the
            # recovery point just stays at the previous checkpoint (loudly
            # — silent RPO decay would be worse than the warning spam)
            import warnings
            monitor.inc('elastic_save_skipped_total')
            tr.event('elastic_save_skipped', step=step,
                     error=type(save_err).__name__)
            warnings.warn(
                "elastic_train_loop: checkpoint save after step %d failed "
                "(%s: %s); continuing — recovery falls back to the "
                "previous checkpoint" % (step, type(save_err).__name__,
                                         save_err), stacklevel=2)
        step += 1
    # flush-on-exit barrier: with async saves the final cadenced save may
    # still be publishing — the loop's contract is that its recovery
    # point is durable when it returns (a deferred publish failure
    # surfaces here rather than being lost with the writer thread)
    flush = getattr(manager, 'flush', None)
    if callable(flush):
        flush()
    return outputs
