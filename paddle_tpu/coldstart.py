"""Where a process' set-up goes: `program_setup_seconds_total{stage,program}`.

A process walks these paths once before it trains or serves, and each
books the seconds it takes under its stage (docs/observability.md
"Reading a cold start"):

    import     the package's own import                     (__init__.py)
    build      the Python front end inside program_guard    (framework.py)
    trace      Program -> jaxpr                             (JAX's event)
    lower      jaxpr -> StableHLO                           (JAX's event)
    compile    XLA's compile, the persistent cache missed   (JAX's event)
    cache_load a cached executable read and deserialised    (JAX's event)
    place      state put onto the device, sharded, re-laid  (the branches
               of executor.py and parallel/spmd.py that move an array)
    first_run  the rest of a frame round a new entry's first call: its
               dispatch — the execution itself is NOT waited for

A stage is a FRAME, `stage(name, program)`: a `monitor.phase` — self time,
so the stages of one thread add up to its wall time, and a
'paddle_tpu:setup.<stage>' event in a live profiler session — whose self
time is split by what JAX reports of its own work inside it. JAX's
durations (`jax.monitoring`, one listener, `listen()`) nest as its
tracing does — every inner `jit` fires inside the outer function's own
duration, a cache retrieval inside the backend compile — so each is taken
as self time too; the parts of a frame are scaled down where they would
pass it, never the other way. A duration that arrives outside every frame
and phase of the package (a user's own `jit`, the benchmark's weights) is
seconds of the process and not of the package: it stays out of the series,
in a table of this module (`outside()`), so no reader has to leave it out.

`compile_frame` is the frame round a new entry's first call, the one
place that observes `compile_seconds` and records the ring's `compile`
span. Nothing here is on a steady path: a frame opens where an entry is
made or an array moves, and nowhere else.
"""
import re
import threading
import time

from . import monitor

# JAX's duration events -> the stage each is booked under. The first three
# come from one context manager (jax._src.dispatch.log_elapsed_time) that
# also records a scalar at ENTRY: that is what lets a nested event be taken
# out of the one round it. The retrieval has no entry record, and nothing
# nests inside it.
_NESTING = {
    '/jax/core/compile/jaxpr_trace_duration': 'trace',
    '/jax/core/compile/jaxpr_to_mlir_module_duration': 'lower',
    '/jax/core/compile/backend_compile_duration': 'compile',
}
_RETRIEVAL = '/jax/compilation_cache/cache_retrieval_time_sec'

_open_events = {}       # thread id -> [[event, seconds nested inside], ...]
_outside = {}           # stage -> JAX's seconds outside every phase
_listening = [False]

# eight stages a program, and a process may hold a hundred programs
monitor.set_series_cap('program_setup_seconds_total', 1024)


def label_of(program):
    """`Program.name` (or the string given) in the characters a compiled
    module's name may have: the `program` label, and what
    lowering.name_after calls the jitted function."""
    name = getattr(program, 'name', program)
    return re.sub(r'[^0-9A-Za-z_.-]', '_', str(name))


def outside():
    """JAX's seconds by stage that fell outside every frame and phase of
    the package since the listener was registered: the process' own."""
    return dict(_outside)


def book(stage, seconds, program=None):
    labels = {'stage': stage}
    if program is not None:
        labels['program'] = program
    monitor.inc('program_setup_seconds_total', seconds, labels)


class _Frame(monitor._Phase):
    """One stage of set-up on one thread (module docstring). `parts`:
    JAX's seconds inside the frame by stage, booked with the frame's own
    remainder when it closes — under the name the program has THEN: a
    builder names the program it builds into inside the guard."""

    __slots__ = ('stage', 'program', 'parts')

    def __init__(self, stage, program, known):
        monitor._Phase.__init__(self, known)
        self.stage, self.program, self.parts = stage, program, {}

    def __exit__(self, *exc):
        monitor._Phase.__exit__(self, *exc)
        own = max(0.0, self.dur_s - self.nested_s)
        program = label_of(self.program)
        inside = sum(self.parts.values())
        if inside > 0.0:
            # JAX's clock is time.time(), the frame's perf_counter: parts
            # that would pass the frame are cut to it
            scale = min(1.0, own / inside)
            for stage, seconds in self.parts.items():
                book(stage, seconds * scale, program)
            own -= inside * scale
        book(self.stage, own, program)
        return False


def stage(name, program, counter=None, labels=None):
    """The frame of stage `name` for `program` (a Program or a name).
    `counter`/`labels`: a phase counter of another family that the frame
    stands for as well (Executor.run's `compile` phase), given the
    frame's self time whole."""
    return _Frame(name, program,
                  monitor.phase_series('setup.' + name, counter, labels))


class compile_frame(object):
    """The frame round the first call of a newly made entry — trace,
    lower, compile or cache load, and the first execution's dispatch —
    for every path that makes one: a `first_run` frame (`stage`: another, for a
    site that only builds), the ring's `compile` span
    (profiler.export_chrome_tracing shows it with no session) and one
    observation of `compile_seconds`, counted from `since` where the
    entry's making began before the frame (goodput's `compile` loss
    bucket reads the histogram). `seconds`, set on exit, is what was
    observed."""

    __slots__ = ('_frame', '_span', '_since', 'seconds')

    def __init__(self, program, stage_name='first_run', since=None,
                 counter=None, labels=None):
        self._frame = stage(stage_name, program, counter, labels)
        self._span = monitor.span('compile')
        self._since = since

    def __enter__(self):
        if self._since is None:
            self._since = time.perf_counter()
        self._frame.__enter__()
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        self._frame.__exit__(*exc)
        self.seconds = time.perf_counter() - self._since
        monitor.observe('compile_seconds', self.seconds)
        return False


def _attribute(stage_name, seconds):
    """JAX's `seconds` of `stage_name` on this thread, given to the frame
    they fall in: the nearest one round the innermost open phase. Inside a
    phase that no frame holds (Executor.run's prepare making its first
    PRNG key) they are the package's all the same, booked under that
    phase's name; outside every phase, the process' own. A phase of
    another family keeps its self time whole either way: its readers add
    phases up to a wall time of their own."""
    inner = monitor._open_phase.get(threading.get_ident())
    if inner is None:
        with monitor._lock:
            _outside[stage_name] = _outside.get(stage_name, 0.0) + seconds
        return
    frame = inner
    while frame is not None and not isinstance(frame, _Frame):
        frame = frame._outer
    if frame is None:
        book(stage_name, seconds,
             inner.known[3][len(monitor.ANNOTATION_PREFIX):])
        return
    frame.parts[stage_name] = frame.parts.get(stage_name, 0.0) + seconds
    if inner is not frame:
        # they fell in a phase nested in the frame, whose whole duration
        # the frame's self time leaves out: the frame takes them back
        frame.nested_s -= seconds


def _on_scalar(event, value, **_kw):
    if event in _NESTING:
        _open_events.setdefault(threading.get_ident(), []).append(
            [event, 0.0])


def _on_duration(event, duration, **_kw):
    stage_name = _NESTING.get(event)
    if stage_name is None:
        if event != _RETRIEVAL:
            return
        stage_name = 'cache_load'
    tid = threading.get_ident()
    open_ = _open_events.get(tid)
    own = duration
    if event != _RETRIEVAL and open_:
        # the entry this duration closes; one above it never closed
        while open_:
            entered, nested = open_.pop()
            if entered == event:
                own = max(0.0, duration - nested)
                break
    if open_:
        open_[-1][1] += duration
    elif open_ is not None:
        del _open_events[tid]
    _attribute(stage_name, own)


def listen():
    """Register the listener with jax.monitoring, once a process; called
    where the persistent compile cache is wired, ahead of the first
    compile."""
    if _listening[0]:
        return
    _listening[0] = True
    import jax
    jax.monitoring.register_scalar_listener(_on_scalar)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
