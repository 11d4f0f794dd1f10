"""Names for what the chip and the host are doing, from inside the engine.

Three things, all riding what JAX already has — no flag, environment
variable, exporter or telemetry event of their own:

- **Stage scopes** (``stage`` / ``staged``): ``jax.named_scope`` under
  the names the in-kernel work counters use — ``ptt.expand``,
  ``ptt.probe``, ``ptt.compact``, ``ptt.append`` — plus
  ``ptt.levelctl`` (the level kernel's loop control, boundary
  bookkeeping, the packed stats vector and the frontier-window shift),
  ``ptt.rehash`` (table growth), ``ptt.grow`` (row-store and log
  growth), ``ptt.seed`` (seed merge/write) and ``ptt.init``
  (initial-state generation), and in the liveness run
  (``engine/liveness.py``) ``ptt.live_table``, ``ptt.live_goal``,
  ``ptt.sweep_expand``, ``ptt.sweep_join``, ``ptt.sweep_prop`` and
  ``ptt.sweep_compact``, and under a device-memory budget (the tiered
  store's programs, ``engine/device_bfs.py``) ``ptt.spill_tag``,
  ``ptt.spill_evict``, ``ptt.spill_sieve``, ``ptt.spill_unflag``,
  ``ptt.spill_shift`` and ``ptt.spill_fetch`` (the rehash at the same
  size after an eviction is ``ptt.rehash``), and in a simulation
  (``sim/engine.py``) ``ptt.sim_init`` (a round's fresh initial
  states), ``ptt.sim_expand`` (``model.successors`` and
  ``stutter_enabled``: the lanes' guards alone where the model builds
  the drawn successor itself), ``ptt.sim_choose`` (the keys, the draw,
  the drawn lane's successor, built or selected), ``ptt.sim_inv``,
  ``ptt.sim_dup`` (the duplicate estimator) and ``ptt.sim_replay``.  A scope is HLO metadata
  only: it lands in every operation's ``op_name`` path, which a device
  trace carries for each ``XLA Ops`` event (the ``tf_op`` stat of its
  metadata), and changes nothing that is compiled.  An operation belongs to the innermost ``ptt.`` scope of
  its path.  (Metadata is not part of JAX's persistent-cache key, so
  the jitted functions that carry scopes are named ``ptt_*``: a cache
  written before they had scopes misses by module name instead of
  handing back executables without them — docs/observability.md.)
- **Part scopes** (``part``): a second level under a stage scope, for
  the parts of ONE stage's work — ``jax.named_scope("part.<name>")``,
  entered with an inline ``with`` where the work is written
  (``ops/fpset.py``: the parts of a probe round, ``gather``,
  ``claims_fill``, ``claims_bid``, ``write``, ``reread``, and the
  ladder's ``narrow``).  The prefix holds no ``ptt.``, so whatever
  reads stages reads what it read before parts existed.  An
  operation's stage is the innermost ``ptt.`` scope of its path, as
  above; its part is the innermost part scope BELOW that stage scope,
  else none: a probe round traced by the rehash is stage ``rehash``,
  part ``claims_fill``.  Metadata only, like a stage scope, and under
  the same cache-key rule: the programs that trace a part were
  renamed when the parts came (``ptt_level2`` ...).
- **Host spans** (``span`` / ``spanned`` / ``PhaseClock``):
  ``jax.profiler.TraceAnnotation`` named ``ptt:<name>``, so they lie
  in the same ``.xplane.pb`` and on the same clock as the device's
  operations; with no trace running one costs under a microsecond
  (a phase of the clock about three).  A ``PhaseClock`` also adds each phase's seconds up,
  exclusively (an inner phase pauses the outer), so the phases of one
  ``run()`` sum to its wall.  Inside the phase ``spill`` plain spans
  name what the host does there (``ptt:spill.sieve_wait``, ``.fetch``,
  ``.lookup``, ``.evict``, ``.rows``, ``.join``): they split the
  phase's idle time and add nothing to the clock.  Two overlays of the
  clock say what a phase's seconds are made of, ``dispatch`` first:
  ``clock.call(<program>)`` around ONE call of ONE jitted program
  (span ``ptt:call.<program>``; the program's calls, the seconds from
  entry to return of the call, which is the asynchronous launch and
  not the device's work, and the compile meter's seconds inside it)
  and ``clock.upload(<program>, n)`` around the making of ``n`` host
  values into device arrays for it (span ``ptt:upload``).  Both are
  entered with an inline ``with`` at the site, never through a wrapper
  between the site and the program, and neither touches a phase's
  seconds: ``call_stats()`` takes them out of the phase's total and
  what is left is the engine's own Python.
- **The compile meter** (``compile_meter``): one process-wide
  ``jax.monitoring`` listener, registered on first use, that counts per
  calling thread how often JAX traced, lowered, compiled or loaded from
  the persistent cache, and the seconds each took.  An orthogonal cut
  of the phases, not one of them: a first call of a jitted function
  spends its trace/lower/compile seconds inside whichever phase made
  the call (``dispatch``, mostly).
  The meter also carries a counter the engine's program units report
  (``engine/units.py``): ``jit_body_traces``, the units whose Python
  body ran (misses).

This module is the only place of the package that constructs a scope,
a ``TraceAnnotation`` or a ``jax.monitoring`` listener.
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Dict, Optional

SPAN_PREFIX = "ptt:"
SCOPE_PREFIX = "ptt."
# no ``ptt.`` in it: a reader of stages (``ptt\.[a-z_]+``) never sees a part
PART_PREFIX = "part."

# the exclusive phases of one DeviceChecker.run(), in the order a run
# meets them; each lands in last_stats as host_<phase>_s
PHASES = (
    "init", "seed_load", "grow", "dispatch", "fetch", "account", "ckpt",
    "spill", "trace_walk", "result",
)

# the exclusive phases of one LivenessChecker.run(): ``explore`` holds
# the inner DeviceChecker.run() (whose own phases stay inside it), the
# rest is the edge sweep and the host's graph analysis; each lands in
# the liveness result's stats as host_<phase>_s
LIVE_PHASES = (
    "explore", "live_table", "live_goal", "sweep_dispatch", "sweep_fetch",
    "sweep_account", "analyse", "result",
)

# the exclusive phases of one StreamingSimulator.run() (sim/engine.py):
# ``dispatch`` and ``fetch`` are a segment's, ``dump`` the behaviours
# on request (replay, the check on the device, rendering, the files);
# each lands in the simulation result's stats as host_<phase>_s
SIM_PHASES = (
    "init", "dispatch", "fetch", "account", "ckpt", "dump", "result",
)


# ------------------------------------------------------- device: scopes

def stage(name: str):
    """``jax.named_scope("ptt.<name>")`` — HLO metadata only."""
    import jax

    return jax.named_scope(SCOPE_PREFIX + name)


def part(name: str):
    """``jax.named_scope("part.<name>")``: a part of the stage whose
    scope it is entered under — HLO metadata only.  Enter it inline
    (``with spans.part("gather"):``), never through a decorator: every
    Python frame above a traced equation is paid on a first check."""
    import jax

    return jax.named_scope(PART_PREFIX + name)


def _under(enter):
    """Decorator: run the function inside the context ``enter()``."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with enter():
                return fn(*args, **kwargs)

        return wrapped

    return deco


def staged(name: str):
    """Decorator: trace the function under the stage scope ``name``."""
    return _under(lambda: stage(name))


# ----------------------------------------------------------- host: spans

_annotation = None  # jax.profiler.TraceAnnotation, on first use


def span(name: str, **fields):
    """A host span ``ptt:<name>`` in the profiler's trace (``fields``
    ride as the event's stats)."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation as _annotation
    return _annotation(SPAN_PREFIX + name, **fields)


# one record a (phase, program) on a clock: the indices of
# [calls, call_s, uploads, upload_s, jit_s]
_CALLS, _CALL_S, _UPLOADS, _UPLOAD_S, _JIT_S = range(5)


class _Call:
    """One call of one program on a clock (``PhaseClock.call``)."""

    __slots__ = ("rec", "jit", "ann", "t", "j")

    def __init__(self, rec, jit, ann):
        self.rec, self.jit, self.ann = rec, jit, ann

    def __enter__(self):
        self.ann.__enter__()
        self.j = self.jit["host_s"]
        self.t = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t
        self.ann.__exit__(*exc)
        rec = self.rec
        rec[_CALLS] += 1
        rec[_CALL_S] += dt
        # the meter counts a trace nested in a trace twice: the part
        # of THIS call that was JAX's is at most the call
        rec[_JIT_S] += min(self.jit["host_s"] - self.j, dt)
        return False


class _Upload:
    """One site's host values made device arrays
    (``PhaseClock.upload``)."""

    __slots__ = ("rec", "n", "ann", "t")

    def __init__(self, rec, n, ann):
        self.rec, self.n, self.ann = rec, n, ann

    def __enter__(self):
        self.ann.__enter__()
        self.t = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t
        self.ann.__exit__(*exc)
        self.rec[_UPLOADS] += self.n
        self.rec[_UPLOAD_S] += dt
        return False


def spanned(name: str):
    """Decorator: run the function under the host span ``name``."""
    return _under(lambda: span(name))


class _Phase:
    __slots__ = ("clock", "name", "ann")

    def __init__(self, clock, name, ann):
        self.clock, self.name, self.ann = clock, name, ann

    def __enter__(self):
        self.clock._push(self.name)
        self.ann.__enter__()
        return self

    def __exit__(self, *exc):
        self.ann.__exit__(*exc)
        self.clock._pop()
        return False


def in_phase(name: str):
    """Method decorator: run under the exclusive host phase ``name`` of
    the current run's clock (``self._clock``, a ``PhaseClock``) — a
    site the host passes once per dispatch, fetch or boundary, never
    per row."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapped(self, *args, **kwargs):
            with self._clock.phase(name):
                return fn(self, *args, **kwargs)

        return wrapped

    return deco


class PhaseClock:
    """Exclusive per-phase seconds of one run, on the monotonic clock.

    ``with clock.phase("dispatch", level=n):`` enters the span
    ``ptt:dispatch`` (carrying ``run_id`` and ``level``) and charges the
    time to ``dispatch``; a phase entered inside it pauses it.

    ``with clock.call("ptt_append"):`` around one call of a jitted
    program and ``with clock.upload("ptt_append", 5):`` around the
    making of its five host scalars into device arrays are overlays:
    they count and time under whatever phase is open (``calls``) and
    leave its seconds alone.  No phase is entered inside one.  Used
    from the run's own thread only."""

    def __init__(self, run_id: Optional[str] = None):
        self.run_id = run_id or ""
        self.t0 = time.perf_counter()
        self.seconds: Dict[str, float] = {}
        self._stack = []  # [name, charged-up-to]
        # {phase: {program: [calls, call_s, uploads, upload_s, jit_s]}}
        # of the overlays, by the phase open at the site ("" for none)
        self.calls: Dict[str, Dict[str, list]] = {}
        # the longest stretch between two level-boundary records (the
        # first runs from the start of the run) and the level it ends on
        self.level_wall_max_s = 0.0
        self.level_wall_max_at = 0
        self._boundary_t = self.t0
        # the longest single stay in the phase ``grow`` (one inside
        # another is one stay) and the level in whose stretch it fell:
        # the level of the next boundary record, or one past the last
        self.grow_wall_max_s = 0.0
        self.grow_wall_max_at = 0
        self._grow_t = self.t0  # start of the newest stay
        self._grow_longest = 0.0  # longest stay since the last boundary
        self._boundary_level = 0

    def phase(self, name: str, **fields) -> _Phase:
        return _Phase(self, name, span(name, run_id=self.run_id, **fields))

    def _rec(self, program: str) -> list:
        phase = self._stack[-1][0] if self._stack else ""
        by = self.calls.get(phase)
        if by is None:
            by = self.calls[phase] = {}
        rec = by.get(program)
        if rec is None:
            rec = by[program] = [0, 0.0, 0, 0.0, 0.0]
        return rec

    def call(self, program: str) -> _Call:
        """Around ONE call of the jitted program ``program``: the span
        ``ptt:call.<program>``, one more of its calls, the seconds from
        entry to return (the launch; the device works on after it) and
        those of them the compile meter saw JAX trace, lower, compile
        or load in."""
        return _Call(
            self._rec(program), compile_meter()._mine(),
            span("call." + program),
        )

    def upload(self, program: str, n: int) -> _Upload:
        """Around the making of ``n`` host values into device arrays
        (``jnp.int32(...)``, ``jnp.asarray(...)`` of host data) for a
        call of ``program``: the span ``ptt:upload``, and the values
        and seconds on the program's record."""
        return _Upload(
            self._rec(program), n, span("upload", program=program)
        )

    def _push(self, name):
        now = time.perf_counter()
        if self._stack:
            top = self._stack[-1]
            self.seconds[top[0]] = self.seconds.get(top[0], 0.0) + now - top[1]
        if name == "grow" and not self.open("grow"):
            self._grow_t = now
        self._stack.append([name, now])

    def _pop(self):
        now = time.perf_counter()
        name, since = self._stack.pop()
        self.seconds[name] = self.seconds.get(name, 0.0) + now - since
        if self._stack:
            self._stack[-1][1] = now
        if name == "grow" and not self.open("grow"):
            self._grow_longest = max(self._grow_longest, now - self._grow_t)

    def open(self, name: str) -> bool:
        """Whether a phase ``name`` is entered and not yet left (an
        inner phase pauses it, it stays open)."""
        return any(p[0] == name for p in self._stack)

    def _settle_grow(self, level: int) -> None:
        if self._grow_longest > self.grow_wall_max_s:
            self.grow_wall_max_s = self._grow_longest
            self.grow_wall_max_at = int(level)
        self._grow_longest = 0.0

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    def seconds_of(self, name: str) -> float:
        """Seconds charged to ``name`` so far, an open phase included."""
        s = self.seconds.get(name, 0.0)
        if self._stack and self._stack[-1][0] == name:
            s += time.perf_counter() - self._stack[-1][1]
        return s

    def level_boundary(self, level: int) -> None:
        now = time.perf_counter()
        gap, self._boundary_t = now - self._boundary_t, now
        if gap > self.level_wall_max_s:
            self.level_wall_max_s, self.level_wall_max_at = gap, int(level)
        self._settle_grow(level)
        self._boundary_level = int(level)

    def host_seconds(self, phases=PHASES) -> Dict[str, float]:
        """``host_<phase>_s`` for every phase of ``phases`` (0.0 for one
        never entered) and ``host_unaccounted_s`` = the run's wall so
        far less their sum."""
        wall = self.elapsed()
        out = {f"host_{p}_s": self.seconds_of(p) for p in phases}
        out["host_unaccounted_s"] = wall - sum(out.values())
        return out

    def call_stats(self, phase: str = "dispatch") -> Dict[str, object]:
        """What the phase ``phase`` (``dispatch``, or the sweep's
        ``sweep_dispatch``) is made of, from the overlays:
        ``<phase>_calls`` / ``_call_s`` (program calls made under it
        and their seconds), ``_uploads`` / ``_upload_s`` (host values
        made device arrays at its sites), ``_jit_s`` (the part of
        ``_call_s`` that was JAX's own tracing, lowering, compiling or
        cache loading), ``_python_s`` (``host_<phase>_s`` less calls
        and uploads: the engine's Python around them, so the three add
        up to the phase) and ``_by_program``, ``{program: [calls,
        call_s, uploads, upload_s, jit_s]}``.  The overlays entered
        under every OTHER phase are summed in ``calls_by_phase``,
        ``{phase: [calls, call_s, uploads, upload_s]}``, and listed in
        ``programs_by_phase``, ``{phase: {program: [the five]}}`` (both
        keys carry the prefix ``phase`` has before ``dispatch``)."""

        def rounded(rec):
            return [round(v, 6) if isinstance(v, float) else v for v in rec]

        def summed(by, width):
            return [sum(r[i] for r in by.values()) for i in range(width)]

        by = self.calls.get(phase, {})
        calls, call_s, uploads, upload_s, jit_s = summed(by, 5)
        others = {p: b for p, b in self.calls.items() if p != phase}
        prefix = phase[: -len("dispatch")]
        return {
            f"{phase}_calls": calls,
            f"{phase}_call_s": call_s,
            f"{phase}_uploads": uploads,
            f"{phase}_upload_s": upload_s,
            f"{phase}_jit_s": jit_s,
            f"{phase}_python_s": self.seconds_of(phase) - call_s - upload_s,
            f"{phase}_by_program": {k: rounded(r) for k, r in by.items()},
            f"{prefix}calls_by_phase": {
                p: rounded(summed(b, 4)) for p, b in others.items()
            },
            f"{prefix}programs_by_phase": {
                p: {k: rounded(r) for k, r in b.items()}
                for p, b in others.items()
            },
        }

    def stats(self) -> Dict[str, float]:
        """``host_seconds()`` of a ``DeviceChecker.run()`` with its
        ``call_stats()``, the longest level stretch and the longest
        stay in ``grow``."""
        out = self.host_seconds()
        out.update(self.call_stats())
        out["level_wall_max_s"] = self.level_wall_max_s
        out["level_wall_max_at"] = self.level_wall_max_at
        self._settle_grow(self._boundary_level + 1)
        out["grow_wall_max_s"] = self.grow_wall_max_s
        out["grow_wall_max_at"] = self.grow_wall_max_at
        return out


# ------------------------------------------------------ the compile meter

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"

# duration event -> (its count, its seconds) among the counters below
_DURATIONS = {
    _TRACE: ("traces", "trace_s"),
    _LOWER: ("lowerings", "lower_s"),
    _COMPILE: ("compile_requests", "compile_request_s"),
    _CACHE_LOAD: (None, "cache_load_s"),
}

_COUNTERS = (
    "traces", "trace_s", "lowerings", "lower_s", "compile_requests",
    "compile_request_s", "cache_hits", "cache_misses", "cache_load_s",
    "body_traces",
    # trace_s + lower_s + compile_request_s as ONE running total (a
    # compile request spans its cache load): what ``PhaseClock.call``
    # reads on entry and on return
    "host_s",
)


class CompileMeter:
    """Counts and seconds of JAX's own compile-path events, per calling
    thread (the daemon runs checks on threads; a listener is called on
    the thread that compiles)."""

    def __init__(self):
        import jax

        self._local = threading.local()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _mine(self) -> Dict[str, float]:
        c = getattr(self._local, "c", None)
        if c is None:
            c = self._local.c = dict.fromkeys(_COUNTERS, 0)
        return c

    def _duration(self, event, secs, **_kw):
        keys = _DURATIONS.get(event)
        if keys is None:
            return
        c = self._mine()
        if keys[0]:
            c[keys[0]] += 1
        c[keys[1]] += secs
        if event != _CACHE_LOAD:
            c["host_s"] += secs

    def _event(self, event, **_kw):
        if event == _CACHE_HIT:
            self._mine()["cache_hits"] += 1
        elif event == _CACHE_MISS:
            self._mine()["cache_misses"] += 1

    def count(self, counter: str) -> None:
        """One more of ``counter`` on the calling thread (the program
        units of ``engine/units.py`` report the runs of their
        bodies)."""
        self._mine()[counter] += 1

    def snapshot(self) -> Dict[str, float]:
        """This thread's counters so far."""
        return dict(self._mine())

    def since(self, before: Dict[str, float]) -> Dict[str, float]:
        """What this thread added since ``before`` (a ``snapshot``), as
        the ``jit_*`` keys of ``last_stats``.  A compile request the
        persistent cache answers is a cache load, not a backend
        compile: JAX's ``backend_compile_duration`` spans both, so
        hits and their retrieval seconds are taken out of it, and the
        four durations add up to ``jit_host_s`` without overlap."""
        now = self._mine()
        d = {k: now[k] - before.get(k, 0) for k in _COUNTERS}
        compile_s = max(d["compile_request_s"] - d["cache_load_s"], 0.0)
        return {
            "jit_traces": int(d["traces"]),
            "jit_trace_s": d["trace_s"],
            "jit_lower_s": d["lower_s"],
            "jit_backend_compiles": int(
                d["compile_requests"] - d["cache_hits"]
            ),
            "jit_compile_s": compile_s,
            "jit_cache_hits": int(d["cache_hits"]),
            "jit_cache_misses": int(d["cache_misses"]),
            "jit_cache_load_s": d["cache_load_s"],
            "jit_host_s": (
                d["trace_s"] + d["lower_s"] + compile_s + d["cache_load_s"]
            ),
            "jit_body_traces": int(d["body_traces"]),
        }


_meter: Optional[CompileMeter] = None
_meter_lock = threading.Lock()


def compile_meter() -> CompileMeter:
    """The process's one meter, registered on first use."""
    global _meter
    if _meter is None:
        with _meter_lock:
            if _meter is None:
                _meter = CompileMeter()
    return _meter
