"""Spans and counters inside the program: one recorder per engine.

``SpanRecorder.phase(name, **ids)`` opens a span that is two things at once:

- a ``jax.profiler.TraceAnnotation``, so that with the profiler on the span
  lands in the profiler's own trace, on the device trace's clock
  (``llmctl trace summarize`` reads it from there);
- a counter on ``time.monotonic()``: on exit the span's SELF time (its
  duration minus its children's) and one call are added to
  ``phases[name]``. Self times of spans that tile a thread's time add up to
  that time, which is what ``InferenceEngine.stats()["phases"]`` relies on.

The recorder also keeps ``in_flight`` (programs dispatched and not yet
fetched) and ``starved_s``: seconds during which the engine had work
(``set_busy(True)``) and nothing was in flight — the program's own estimate of
the time the device sat idle under load — and ``starved_by_phase``, the same
seconds by the innermost span the owner thread had open (``NO_SPAN`` between
spans): what the host was doing while the device had nothing.

Always on; no lock (a sequence number guards ``snapshot()``), no option. It
belongs to ONE thread: the one that made it, until the loop that steps the
engine calls ``bind_thread`` (``InferenceServer._engine_loop``, a fleet
replica's ``_loop``, ``InferenceEngine.run_until_idle``). Everything but
``snapshot()`` is that thread's to call: a ``phase`` opened by any other
thread (an HTTP handler whose cancel fires ``on_finish``) is the bare
annotation and counts nothing. ``snapshot()`` may be read from any thread.

Start-up has ONE recorder a process, ``STARTUP`` (``StartupRecorder``, at the
end of this file): imports, the backend, the compile cache and
``jax.monitoring``'s compile events are the process's, not an engine's. Its
spans are ``llmctl.startup.*``; every program's first call is one
``llmctl.startup.program`` span and one entry of its compile ledger
(``snapshot()["programs"]``), which ``InferenceEngine.stats()["startup"]``
hands on.
"""

from __future__ import annotations

import bisect
import threading
import time

from jax import monitoring
from jax.profiler import TraceAnnotation

from .. import _IMPORT_T0

# upper bounds (ms) of the queue-wait histogram; the last bucket is +inf
QUEUE_WAIT_LE_MS = (1, 2, 5, 10, 20, 50, 100, 150, 200, 300, 400, 500, 750,
                    1000, 1500, 2500, 5000)
# ``starved_by_phase``'s key for starved seconds under no span
NO_SPAN = "(no span)"


class _Span(TraceAnnotation):
    """The annotation itself carries the span's bookkeeping: one object a
    span. ``_seq`` is odd while the recorder's state is being changed, so
    that ``snapshot()`` on another thread can tell a torn read and retry."""

    def __init__(self, rec: "SpanRecorder", name: str, ids: dict):
        super().__init__(name, **ids)
        self._rec, self._name = rec, name

    def __enter__(self):
        super().__enter__()
        rec = self._rec
        rec._seq += 1
        self._children = 0.0
        self._t0 = rec._clock()
        if rec._starved_since is not None:
            rec._flush_starved(self._t0)    # up to here: the span around
        rec._stack.append(self)
        rec._seq += 1
        return self

    def __exit__(self, *exc):
        rec = self._rec
        now = rec._clock()
        took = now - self._t0
        rec._seq += 1
        if rec._starved_since is not None:
            rec._flush_starved(now)         # up to here: this span
        rec._stack.pop()
        if rec._stack:
            rec._stack[-1]._children += took
        cell = rec.phases.get(self._name)
        if cell is None:
            cell = rec.phases[self._name] = [0.0, 0]
        cell[0] += took - self._children
        cell[1] += 1
        rec._seq += 1
        return super().__exit__(*exc)


class SpanRecorder:
    def __init__(self, clock=time.monotonic):
        # the clock is an argument for the tests alone: they step a fake
        # one instead of sleeping against the real one
        self._clock = clock
        self.phases: dict[str, list] = {}      # name -> [self seconds, calls]
        self.in_flight = 0
        self.starved_s = 0.0
        self.starved_by_phase: dict[str, float] = {}   # span name -> seconds
        self._busy = False
        # where the starved stretch that is open began, or was last added up
        self._starved_since: float | None = None
        self._stack: list[_Span] = []
        self._seq = 0
        self._owner = threading.get_ident()

    def bind_thread(self) -> None:
        """The calling thread owns the recorder from here on."""
        self._owner = threading.get_ident()

    def phase(self, name: str, **ids):
        """Context manager: a span named ``name``; ``ids`` go on the
        annotation only (they are formatted when it is made: keep them to
        spans opened once a request or once a dispatch)."""
        if threading.get_ident() != self._owner:
            return TraceAnnotation(name, **ids)
        return _Span(self, name, ids)

    def annotate(self, **ids) -> None:
        """More ids on the innermost open span's annotation, for what is
        known only once the span is under way (a prefill's bucket)."""
        if self._stack and TraceAnnotation.is_enabled():
            self._stack[-1].set_metadata(**ids)

    # -- device starvation ---------------------------------------------------

    def _flush_starved(self, now: float) -> None:
        """Add the open starved stretch up to ``now`` to ``starved_s`` and
        to the innermost open span (the caller has made ``_seq`` odd)."""
        took = now - self._starved_since
        name = self._stack[-1]._name if self._stack else NO_SPAN
        self.starved_s += took
        self.starved_by_phase[name] = self.starved_by_phase.get(
            name, 0.0) + took
        self._starved_since = now

    def _mark(self, in_flight: int, busy: bool) -> None:
        now = self._clock()
        self._seq += 1
        if self._starved_since is not None:
            self._flush_starved(now)
        self.in_flight, self._busy = in_flight, busy
        self._starved_since = now if busy and in_flight == 0 else None
        self._seq += 1

    def dispatched(self) -> None:
        """A program whose result will be fetched went to the device."""
        self._mark(self.in_flight + 1, self._busy)

    def fetched(self) -> None:
        """The result of a dispatched program reached the host."""
        self._mark(max(self.in_flight - 1, 0), self._busy)

    def set_busy(self, busy: bool) -> None:
        """Whether a slot holds a request: starvation is counted only then."""
        if busy != self._busy:
            self._mark(self.in_flight, busy)

    def reset_in_flight(self) -> None:
        """Dispatches whose results will never be fetched (``fail_all``)."""
        self._mark(0, self._busy)

    # -- reading -------------------------------------------------------------

    def snapshot(self) -> dict:
        """{"clock_s", "phases": {name: {"s", "n"}}, "starved_s",
        "starved_by_phase": {name: seconds}}, all
        cumulative; ``clock_s`` is the clock (``time.monotonic()``) now, so that the
        difference of two snapshots carries its own denominator. Spans and
        a starved stretch still open count up to now (a decode wait lasts a
        quarter of a second: left out, two snapshots five seconds apart
        would not add up); ``n`` counts closed spans. ``starved_by_phase``
        adds up to ``starved_s``."""
        for _ in range(16):
            seq = self._seq
            if seq & 1:             # the owner is mid-update: let it finish
                time.sleep(0)
                continue
            out = self._read()
            if self._seq == seq:    # nothing changed under the read
                return out
        # the owner never held still for one read (its updates take
        # microseconds, so this is an owner that died inside one): read
        # unguarded; the span that was changing may count twice or not at all
        return self._read()

    def _read(self) -> dict:
        now = self._clock()
        phases = {k: {"s": v[0], "n": v[1]}
                  for k, v in list(self.phases.items())}
        inner_t0 = now
        stack, since = list(self._stack), self._starved_since
        for span in reversed(stack):
            cell = phases.setdefault(span._name, {"s": 0.0, "n": 0})
            cell["s"] += inner_t0 - span._t0 - span._children
            inner_t0 = span._t0
        starved, by_phase = self.starved_s, dict(self.starved_by_phase)
        if since is not None:
            name = stack[-1]._name if stack else NO_SPAN
            starved += now - since
            by_phase[name] = by_phase.get(name, 0.0) + now - since
        return {"clock_s": now, "phases": phases, "starved_s": starved,
                "starved_by_phase": by_phase}


class QueueWaitHistogram:
    """Fixed-bound histogram of the time a request waited for a slot."""

    def __init__(self):
        self.counts = [0] * (len(QUEUE_WAIT_LE_MS) + 1)
        self.sum_ms = 0.0
        self.n = 0

    def observe(self, ms: float) -> None:
        self.counts[bisect.bisect_left(QUEUE_WAIT_LE_MS, ms)] += 1
        self.sum_ms += ms
        self.n += 1

    def snapshot(self) -> dict:
        return {"le": [*QUEUE_WAIT_LE_MS, "+inf"],
                "counts": list(self.counts), "sum": self.sum_ms, "n": self.n}


# -- start-up: one recorder a process ------------------------------------------

PROGRAM = "llmctl.startup.program"
UNSCOPED = "(unscoped)"
MAX_EVENTS = 512            # closed spans, and ledger entries, kept with stamps

# jax.monitoring's events of one compile (JAX 0.9.0) -> the ledger's field.
# Each is reported with its start and end (``record_event_time_span``).
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
}
_CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class _Ledger:
    """Where one program's first call (or one compile outside any) spent its
    time, by ``jax.monitoring`` event. ``trace_s``: the function traced to a
    jaxpr; ``lower_s``: jaxpr -> MLIR (no cache skips these two);
    ``compile_s``: the backend's compile; ``cache_read_s``: the executable
    read from the persistent cache instead; ``cache_hit``: whether every
    executable was read (None: nothing compiled)."""

    __slots__ = ("t0", "trace_s", "lower_s", "compile_s", "cache_read_s",
                 "cache_hit", "_tops", "_read_s", "_hit")

    def __init__(self, t0: float):
        self.t0 = t0
        self.trace_s = self.lower_s = self.compile_s = 0.0
        self.cache_read_s = 0.0
        self.cache_hit: bool | None = None
        self._tops: list = []       # (field, start, counted) not inside a later one
        self._read_s = 0.0          # cache reads since the last compile event
        self._hit = False           # a cache hit since the last compile event

    def add(self, field: str, start: float, end: float) -> None:
        """An event fires when it ENDS, so what lies inside it has been added
        already (a jitted ``jnp`` function traced inside the program's trace
        fires its own trace event, a function traced by a lowering rule one
        inside the lowering): the outer interval holds that time, take the
        inner one back."""
        tops = self._tops
        while tops and tops[-1][1] >= start:
            inner, _, counted = tops.pop()
            setattr(self, inner, getattr(self, inner) - counted)
        counted = end - start
        if field == "compile_s":
            # the event wraps the cache's lookup: a read is cache_read_s
            counted = max(counted - self._read_s, 0.0)
            hit = self._hit
            self.cache_hit = hit if self.cache_hit is None else (
                self.cache_hit and hit)
            self._read_s, self._hit = 0.0, False
        tops.append((field, start, counted))
        setattr(self, field, getattr(self, field) + counted)

    def cache_read(self, seconds: float) -> None:
        self.cache_read_s += seconds
        self._read_s += seconds

    def entry(self, name: str, t1: float) -> dict:
        s = t1 - self.t0
        named = (self.trace_s + self.lower_s + self.compile_s
                 + self.cache_read_s)
        return {"name": name, "t0": self.t0, "s": s,
                "trace_s": self.trace_s, "lower_s": self.lower_s,
                "compile_s": self.compile_s,
                "cache_read_s": self.cache_read_s,
                "cache_hit": self.cache_hit,
                # the call itself: arguments, dispatch, the wait for a
                # donated buffer; the device's run is not waited for
                "run_s": max(s - named, 0.0)}


class _ThreadState:
    """What one thread has open and has closed. Each thread writes its own,
    so no write races another; ``snapshot()`` adds the threads up."""

    __slots__ = ("stack", "totals", "loose")

    def __init__(self):
        self.stack: list = []                 # open spans, innermost last
        self.totals: dict[str, tuple] = {}    # name -> (self seconds, calls)
        self.loose: _Ledger | None = None     # a compile under no program span


class _StartupSpan(TraceAnnotation):
    """``program`` names the program whose first call the span is: such a
    span carries a ledger, and leaves it as an entry of ``programs``."""

    def __init__(self, rec: "StartupRecorder", name: str, ids: dict,
                 program: str | None = None):
        super().__init__(name, **ids)
        self._rec, self._name, self._program = rec, name, program
        self.ledger: _Ledger | None = None
        self.seconds = 0.0

    def __enter__(self):
        super().__enter__()
        self._state = self._rec._thread()
        self._children = 0.0
        self._t0 = self._rec._clock()
        if self._program is not None:
            self.ledger = _Ledger(self._t0)
        self._state.stack.append(self)
        return self

    def __exit__(self, *exc):
        rec, stack = self._rec, self._state.stack
        t1 = rec._clock()
        self.seconds = t1 - self._t0
        stack.pop()
        if stack:
            stack[-1]._children += self.seconds
        rec._closed(self._state, self._name, self._t0, t1,
                    self.seconds - self._children)
        if self.ledger is not None:
            rec._keep_program(self.ledger.entry(self._program, t1))
        return super().__exit__(*exc)


class StartupRecorder:
    """Where a process's start-up goes: ``llmctl.startup.*`` spans from any
    thread, and the compile ledger. Always on, no option, no lock: a thread
    writes its own totals, and the lists shared between threads are only
    appended to.

    ``phase(name, **ids)`` is a ``TraceAnnotation`` (a profile taken over a
    start shows it with its ids, and ``llmctl trace summarize`` reads it) AND
    an event ``(name, t0, t1, self seconds)`` on the clock of
    ``SpanRecorder.snapshot()["clock_s"]``. The first ``MAX_EVENTS`` events
    keep their stamps, so that a reader can cut at an instant
    (``snapshot(until=...)``); beyond that only the totals grow.

    The ledger: ``listen()`` registers ``jax.monitoring`` listeners once. A
    compile is synchronous in its caller, so an event belongs to the
    innermost ``llmctl.startup.program`` span open ON THE THREAD that fired
    it; with none open it goes to an entry named ``(unscoped)``, one a
    compile (closed by the backend's compile event), so that nothing
    compiled in the process is invisible."""

    def __init__(self, clock=time.monotonic, import_t0: float | None = None):
        self._clock = clock
        # the package's first line (``__init__.py``), not this module's
        self.import_t0 = clock() if import_t0 is None else import_t0
        self.ready_t: float | None = None
        self._local = threading.local()
        self._threads: list[_ThreadState] = []
        self._events: list[tuple] = []
        self._programs: list[dict] = []
        self._notes: dict[str, dict] = {}
        self._imported = False
        self._listening = False

    def _thread(self) -> _ThreadState:
        state = getattr(self._local, "state", None)
        if state is None:
            state = self._local.state = _ThreadState()
            self._threads.append(state)
        return state

    # -- writing -------------------------------------------------------------

    def phase(self, name: str, **ids) -> _StartupSpan:
        return _StartupSpan(self, name, ids)

    def program(self, name: str) -> _StartupSpan:
        """The span around a program's FIRST call (trace, lowering, compile
        or cache read, dispatch): its entry in ``programs``."""
        return _StartupSpan(self, PROGRAM, {"name": name}, program=name)

    def note(self, name: str, **facts) -> None:
        """A static fact of this start, such as the path a program was
        traced with: ``snapshot()["notes"][name]``, the latest kept."""
        self._notes[name] = facts

    def imported(self) -> None:
        """An entry module has finished importing: closes
        ``llmctl.startup.import``, open since the package's first line, less
        the start-up spans closed inside it. Once a process."""
        if self._imported:
            return
        self._imported = True
        now = self._clock()
        inside = sum(e[3] for e in list(self._events))
        self._closed(self._thread(), "llmctl.startup.import", self.import_t0,
                     now, max(now - self.import_t0 - inside, 0.0))

    def ready(self) -> None:
        """The process serves (or has finished its first training step):
        what starts after this is not start-up."""
        if self.ready_t is None:
            self.ready_t = self._clock()

    def _closed(self, state, name, t0, t1, self_s) -> None:
        s, n = state.totals.get(name, (0.0, 0))
        state.totals[name] = (s + self_s, n + 1)
        if len(self._events) < MAX_EVENTS:
            self._events.append((name, t0, t1, self_s))

    def _keep_program(self, entry: dict) -> None:
        if len(self._programs) < MAX_EVENTS:
            self._programs.append(entry)

    # -- the compile ledger --------------------------------------------------

    def listen(self) -> None:
        if not self._listening:
            self._listening = True
            monitoring.register_event_time_span_listener(self._on_time_span)
            monitoring.register_event_duration_secs_listener(self._on_duration)
            monitoring.register_event_listener(self._on_event)

    def _ledger(self, state: _ThreadState, started_ago: float = 0.0
                ) -> _Ledger:
        """Where an event fired on ``state``'s thread belongs."""
        for span in reversed(state.stack):
            if span.ledger is not None:
                return span.ledger
        if state.loose is None:
            state.loose = _Ledger(self._clock() - started_ago)
        return state.loose

    def _on_time_span(self, event: str, start: float, end: float, **_) -> None:
        field = _COMPILE_EVENTS.get(event)
        if field is None:
            return
        state = self._thread()
        ledger = self._ledger(state, end - start)
        ledger.add(field, start, end)
        if field == "compile_s" and ledger is state.loose:
            state.loose = None
            self._keep_program(ledger.entry(UNSCOPED, self._clock()))

    def _on_duration(self, event: str, seconds: float, **_) -> None:
        if event == _CACHE_READ_EVENT:
            self._ledger(self._thread(), seconds).cache_read(seconds)

    def _on_event(self, event: str, **_) -> None:
        if event == _CACHE_HIT_EVENT:
            self._ledger(self._thread())._hit = True

    # -- reading -------------------------------------------------------------

    def snapshot(self, until: float | None = None) -> dict:
        """{"clock_s", "import_t0", "ready_t", "phases": {name: {"s", "n"}},
        "programs": [{"name", "t0", "s", "trace_s", "lower_s", "compile_s",
        "cache_read_s", "cache_hit", "run_s"}], "notes": {name: facts}} of
        the CLOSED spans, from any thread. ``until`` keeps what had ended by
        that instant (of the events that kept their stamps)."""
        phases: dict = {}
        if until is None:
            cells = [(name, s, n) for state in list(self._threads)
                     for name, (s, n) in list(state.totals.items())]
        else:
            cells = [(name, self_s, 1)
                     for name, _, t1, self_s in list(self._events)
                     if t1 <= until]
        for name, s, n in cells:
            cell = phases.setdefault(name, {"s": 0.0, "n": 0})
            cell["s"] += s
            cell["n"] += n
        return {"clock_s": self._clock(), "import_t0": self.import_t0,
                "ready_t": self.ready_t, "phases": phases,
                "notes": dict(self._notes),
                # (an entry is never changed once it is kept)
                "programs": [p for p in list(self._programs)
                             if until is None or p["t0"] + p["s"] <= until]}

    def summary(self, top: int = 3) -> str:
        """One line for the log: total, the phases, the ``top`` most
        expensive programs."""
        snap = self.snapshot()
        end = snap["ready_t"] if snap["ready_t"] is not None else snap[
            "clock_s"]
        phases = ", ".join(
            f"{name.removeprefix('llmctl.startup.')} {cell['s']:.2f}"
            + (f" x{cell['n']}" if cell["n"] > 1 else "")
            for name, cell in sorted(snap["phases"].items(),
                                     key=lambda kv: -kv[1]["s"]))
        programs = sorted((p for p in snap["programs"]
                           if p["name"] != UNSCOPED),
                          key=lambda p: -p["s"])[:top]
        costly = "; ".join(
            f"{p['name']} {p['s']:.2f} (trace {p['trace_s']:.2f}, lower "
            f"{p['lower_s']:.2f}, compile {p['compile_s']:.2f}, cache read "
            f"{p['cache_read_s']:.2f})" for p in programs)
        notes = "".join(
            f" | {name}: " + ", ".join(f"{k} {v}" for k, v in facts.items())
            for name, facts in snap["notes"].items())
        return (f"start-up {end - snap['import_t0']:.2f} s since the package "
                f"was imported: {phases or 'no spans'}"
                + (f" | most expensive programs: {costly}" if costly else "")
                + notes)


STARTUP = StartupRecorder(import_t0=_IMPORT_T0)
STARTUP.listen()
