"""Event-level serving trace: a bounded ring of spans and instants.

``Tracer`` is the low-overhead recorder the serving stack threads its
hooks through (``serve/engine.py``, ``serve/scheduler.py``,
``tune/dispatch.py``).  Design constraints, in order:

  * **cheap when off** — engines hold a :data:`NULL` tracer by default;
    every hook is a no-op method call, no branching at call sites;
  * **bounded** — events land in a ring buffer (``capacity`` newest
    kept, ``dropped`` counts the rest), so a week-long serve cannot OOM
    the host because someone left tracing on;
  * **one clock** — a tracer on the default clock stamps microseconds
    since :data:`ORIGIN`, one ``time.perf_counter`` reading taken when
    this module is imported, so every such tracer shares one timeline
    and a tracer attached late can hold earlier events (program builds)
    at non-negative times;
  * **deterministic under test** — the clock is injectable (tests pass
    a fake); an injected clock counts from tracer construction;
  * **schema-versioned** — every exported artifact carries
    :data:`SCHEMA_VERSION` so downstream consumers (Perfetto loaders,
    the perf-trajectory gate, future async-loop debugging) can detect
    drift.

Program builds are recorded from JAX's own backend-compile event (a
persistent-cache load passes through it too): :func:`install_build_listener`
keeps each build in the bounded process log :data:`BUILDS` and writes
it to the active tracer as a ``compile`` span on ``engine/compile``;
:func:`replay_builds` writes the logged builds into a tracer attached
after them.

Events are plain dicts (see :meth:`Tracer.emit`) with two shapes:
complete spans (``ph == "X"``, with ``dur``) and instants
(``ph == "i"``).  Every event lives on a *track*: ``"engine/<phase>"``
for engine phases (tick, admission, prefix, prefill, decode, sync,
sample, preempt, evict, kernel, compile) or ``"req/<uid>"`` for
per-request timelines.  A request's lane holds three spans that tile
its wait for the first token (:meth:`Tracer.phase`): ``queue`` (submit
to first admission), ``prefill_wait`` (to the dispatch of its first
prefill chunk) and ``prefill`` (to the emission of its first token).
``obs/export.py`` maps tracks onto Chrome trace-event process/thread
lanes.

Double-buffered ticks (``PagedServeEngine.step_async``) interleave the
lanes on purpose: tick N's ``decode_dispatch`` span (``engine/decode``,
``mode="async"``) precedes tick N-1's ``device_sync`` span inside the
same ``tick`` span — the overlap the async host loop exists for is
directly visible as that ordering.  Sync spans carry ``sync_tick`` (the
tick whose tokens they wait for) and token instants on ``req/<uid>``
tracks consequently land one tick after their ``decode_dispatch``; the
tick-top deadline sweep and cancellations add ``deadline`` / ``fail``
instants on the request track.

The module-level *active tracer* is how code that cannot be handed a
tracer instance (the ``tune.dispatch`` config resolver, called from
deep inside op wrappers) still records: engines ``set_active`` their
tracer at construction and dispatch calls
:func:`record_kernel_config`, which no-ops unless a tracer is active.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from contextlib import contextmanager, nullcontext
from typing import Dict, List, Optional

SCHEMA_VERSION = 1

#: the process clock's zero: every tracer on the default clock stamps
#: microseconds since this ``time.perf_counter`` reading
ORIGIN = time.perf_counter()

# the engine-phase track catalogue; export groups these into one
# process lane, in this order
ENGINE_TRACKS = (
    "engine/tick", "engine/admission", "engine/prefix", "engine/prefill",
    "engine/decode", "engine/sync", "engine/sample", "engine/preempt",
    "engine/evict", "engine/kernel", "engine/compile",
)


def req_track(uid) -> str:
    """The per-request track name for a request uid."""
    return f"req/{uid}"


class _Span:
    """Class-based context manager for :meth:`Tracer.span` — spans are
    the tracer's hottest path (several per engine tick) and a generator
    contextmanager costs ~3x more per entry than this slotted object,
    which matters for the <= 5% trace-overhead budget the serving bench
    enforces."""

    __slots__ = ("tr", "name", "track", "cat", "args", "t0", "bridge")

    def __init__(self, tr, name, track, cat, args):
        self.tr = tr
        self.name = name
        self.track = track
        self.cat = cat
        self.args = args
        self.bridge = None

    def __enter__(self):
        tr = self.tr
        if tr._annotation is not None:
            self.bridge = tr._annotation(self.name)
            self.bridge.__enter__()
        self.t0 = tr.now_us()
        return tr

    def __exit__(self, *exc):
        tr = self.tr
        tr.emit(self.name, "X", self.t0, self.track, self.cat,
                dur=tr.now_us() - self.t0, args=self.args)
        if self.bridge is not None:
            self.bridge.__exit__(*exc)
        return False


class Tracer:
    """Span/instant recorder over an injectable clock and a ring buffer.

    ``capacity`` bounds retained events (newest win); ``profiler_bridge``
    additionally wraps every span in a ``jax.profiler.TraceAnnotation``
    so host spans line up with device profiles captured via
    ``jax.profiler.trace`` (jax is imported only then).  ``origin`` is
    the clock reading timestamps count from: :data:`ORIGIN` on the
    default clock, the construction time on an injected one.
    """

    def __init__(self, clock=time.perf_counter, capacity: int = 1 << 16,
                 profiler_bridge: bool = False):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.clock = clock
        self.capacity = capacity
        self.origin = ORIGIN if clock is time.perf_counter else clock()
        self.builds_seen = 0        # newest BUILDS record written here
        self._buf: deque = deque(maxlen=capacity)
        self.total = 0              # events ever emitted (incl. dropped)
        self.tick: int = -1         # engine tick, tagged onto every event
        self._annotation = None
        if profiler_bridge:
            from jax.profiler import TraceAnnotation
            self._annotation = TraceAnnotation

    # ------------------------------------------------------------------
    def now_us(self) -> float:
        """Microseconds since :attr:`origin`."""
        return (self.clock() - self.origin) * 1e6

    def at_us(self, t: float) -> float:
        """This tracer's timestamp of ``time.perf_counter`` reading ``t``
        (an injected clock is assumed to tick at the same rate)."""
        if self.clock is time.perf_counter:
            return (t - self.origin) * 1e6
        return self.now_us() - (time.perf_counter() - t) * 1e6

    def emit(self, name: str, ph: str, ts: float, track: str,
             cat: str = "engine", dur: Optional[float] = None,
             args: Optional[dict] = None) -> None:
        ev = {"name": name, "ph": ph, "ts": ts, "track": track, "cat": cat}
        if dur is not None:
            ev["dur"] = dur
        a = dict(args) if args else {}
        if self.tick >= 0 and "tick" not in a:
            a["tick"] = self.tick
        if a:
            ev["args"] = a
        self._buf.append(ev)
        self.total += 1

    def instant(self, name: str, *, track: str = "engine/tick",
                cat: str = "engine", **args) -> None:
        self.emit(name, "i", self.now_us(), track, cat, args=args)

    def span(self, name: str, *, track: str = "engine/tick",
             cat: str = "engine", **args) -> "_Span":
        """Record a complete span (``ph == "X"``) around the body."""
        return _Span(self, name, track, cat, args)

    def phase(self, name: str, uid, t0: float, t1: float) -> None:
        """Record a closed phase ``[t0, t1]`` (microseconds on this
        tracer's clock) of request ``uid`` on its lane.  Never bridged to
        the profiler: a phase overlaps host work of other requests."""
        self.emit(name, "X", t0, req_track(uid), "request", dur=t1 - t0,
                  args={"uid": uid})

    # ------------------------------------------------------------------
    @property
    def events(self) -> List[dict]:
        return list(self._buf)

    @property
    def dropped(self) -> int:
        return self.total - len(self._buf)

    def tracks(self) -> List[str]:
        """Distinct tracks with at least one event, engine lanes first
        (catalogue order), then request lanes by first appearance."""
        seen: Dict[str, None] = {}
        for ev in self._buf:
            seen.setdefault(ev["track"], None)
        eng = [t for t in ENGINE_TRACKS if t in seen]
        eng += [t for t in seen if t.startswith("engine/")
                and t not in ENGINE_TRACKS]
        return eng + [t for t in seen if not t.startswith("engine/")]

    def clear(self) -> None:
        self._buf.clear()
        self.total = 0


class NullTracer:
    """API-compatible no-op: engines hold this when tracing is off so
    hook call sites stay branch-free.  ``span`` hands back a shared
    null context; nothing is ever recorded."""

    tick = -1
    capacity = 0
    total = 0
    builds_seen = None
    dropped = 0
    events: List[dict] = []

    def emit(self, *a, **kw) -> None:
        pass

    def instant(self, *a, **kw) -> None:
        pass

    def span(self, *a, **kw):
        return nullcontext()

    def now_us(self) -> float:
        return 0.0

    def phase(self, *a) -> None:
        pass

    def tracks(self) -> List[str]:
        return []

    def clear(self) -> None:
        pass


NULL = NullTracer()

# ---------------------------------------------------------------------------
# active tracer: the escape hatch for call sites that cannot be handed a
# tracer instance (kernel-config resolution inside op wrappers)
# ---------------------------------------------------------------------------

_ACTIVE: Optional[Tracer] = None


def set_active(tracer: Optional[Tracer]) -> None:
    global _ACTIVE
    _ACTIVE = tracer


def get_active() -> Optional[Tracer]:
    return _ACTIVE


@contextmanager
def activate(tracer: Optional[Tracer]):
    prev = get_active()
    set_active(tracer)
    try:
        yield tracer
    finally:
        set_active(prev)


def record_kernel_config(kernel: str, source: str, config, **meta) -> None:
    """Record one kernel-launch config resolution on the active tracer.

    Called by ``tune.dispatch.kernel_config`` at every resolution point
    so traces show which launches ran a *tuned* config and which fell
    back to the *heuristic* (``source``: ``"cache"`` | ``"tuned"`` |
    ``"heuristic"``).  Dispatch runs eagerly while jit traces, so these
    events mark (re)compilations, not per-tick launches.  No-op without
    an active tracer.
    """
    t = _ACTIVE
    if t is None:
        return
    t.instant(f"kernel_config:{kernel}", track="engine/kernel",
              cat="kernel", kernel=kernel, source=source,
              config=config.to_dict(), **meta)


def record_kernel_unsupported(kernel: str, reason: str, **meta) -> None:
    """Record one failed capability negotiation on the active tracer.

    Called by ``tune.dispatch.kernel_unsupported_reason`` when a probe
    rejects a kernel for a problem, with the SPECIFIC cap that failed
    (``"window"``, ``"kv_dtype"``, ``"latent"``, ``"tp"``, ...) — so a
    trace of a gathered-fallback run says *why* it gathered instead of
    collapsing every reason into one boolean.  No-op without an active
    tracer.
    """
    t = _ACTIVE
    if t is None:
        return
    t.instant(f"kernel_unsupported:{kernel}", track="engine/kernel",
              cat="kernel", kernel=kernel, reason=reason, **meta)


def record_backend_fallback(preference: str, backend: str,
                            **meta) -> None:
    """Record one quantized linear whose kernel preference resolved to
    an XLA path (``bcq_xla`` / ``dense``) as a ``backend_fallback``
    instant on the active tracer.

    Called by ``quant.backends.resolve_backend`` while jit traces, so
    once per compiled linear.  No-op without an active tracer.
    """
    t = _ACTIVE
    if t is None:
        return
    t.instant("backend_fallback", track="engine/kernel", cat="kernel",
              preference=preference, backend=backend, **meta)


# ---------------------------------------------------------------------------
# program builds
# ---------------------------------------------------------------------------

#: JAX's backend-compile duration event (``jax._src.dispatch``), which a
#: persistent-cache load passes through as well, and the cache-hit event
#: that precedes it inside such a load
BUILD_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class BuildLog:
    """The process's newest ``capacity`` program builds, each a dict:
    ``seq`` (1, 2, ... over the process), ``fun_name``, ``start``
    (``time.perf_counter`` seconds), ``seconds``, ``cache_hit`` and, when
    a tracer was active, its ``tick``.  ``total`` counts every build."""

    def __init__(self, capacity: int = 4096):
        self.records: deque = deque(maxlen=capacity)
        self.total = 0
        self._hit = threading.local()       # set by the cache's events
        self._lock = threading.Lock()

    def on_event(self, event: str, **kw) -> None:
        if event == CACHE_HIT_EVENT:
            self._hit.value = True

    def on_duration(self, event: str, duration: float, **kw) -> None:
        if event != BUILD_EVENT:
            return
        end = time.perf_counter()
        rec = {"fun_name": str(kw.get("fun_name", "?")),
               "start": end - duration, "seconds": duration,
               "cache_hit": getattr(self._hit, "value", False)}
        self._hit.value = False
        t = _ACTIVE
        if t is not None and t.tick >= 0:
            rec["tick"] = t.tick
        with self._lock:
            self.total += 1
            rec["seq"] = self.total
            self.records.append(rec)
        if t is not None and t.builds_seen is not None:
            _write_build(t, rec)


BUILDS = BuildLog()
_installed = False


def install_build_listener() -> None:
    """Listen to JAX's build events once per process (idempotent)."""
    global _installed
    if _installed:
        return
    from jax import monitoring
    monitoring.register_event_listener(BUILDS.on_event)
    monitoring.register_event_duration_secs_listener(BUILDS.on_duration)
    _installed = True


def _write_build(tracer, rec: dict) -> None:
    args = {"fun_name": rec["fun_name"], "cache_hit": rec["cache_hit"]}
    if "tick" in rec:
        args["tick"] = rec["tick"]
    tracer.emit("compile", "X", tracer.at_us(rec["start"]), "engine/compile",
                "compile", dur=rec["seconds"] * 1e6, args=args)
    tracer.builds_seen = rec["seq"]


def replay_builds(tracer) -> None:
    """Write the logged builds ``tracer`` has not yet seen into it."""
    seen = tracer.builds_seen
    if seen is None:                     # the NullTracer
        return
    for rec in list(BUILDS.records):
        if rec["seq"] > seen:
            _write_build(tracer, rec)
