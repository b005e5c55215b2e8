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
the time the device sat idle under load.

Always on; no lock (a sequence number guards ``snapshot()``), no option. It
belongs to ONE thread: the one that made it, until the loop that steps the
engine calls ``bind_thread`` (``InferenceServer._engine_loop``, a fleet
replica's ``_loop``, ``InferenceEngine.run_until_idle``). Everything but
``snapshot()`` is that thread's to call: a ``phase`` opened by any other
thread (an HTTP handler whose cancel fires ``on_finish``) is the bare
annotation and counts nothing. ``snapshot()`` may be read from any thread.
"""

from __future__ import annotations

import bisect
import threading
import time

from jax.profiler import TraceAnnotation

# upper bounds (ms) of the queue-wait histogram; the last bucket is +inf
QUEUE_WAIT_LE_MS = (1, 2, 5, 10, 20, 50, 100, 150, 200, 300, 400, 500, 750,
                    1000, 1500, 2500, 5000)


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
        self._t0 = time.monotonic()
        rec._stack.append(self)
        rec._seq += 1
        return self

    def __exit__(self, *exc):
        rec = self._rec
        took = time.monotonic() - self._t0
        rec._seq += 1
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
    def __init__(self):
        self.phases: dict[str, list] = {}      # name -> [self seconds, calls]
        self.in_flight = 0
        self.starved_s = 0.0
        self._busy = False
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

    def _mark(self, in_flight: int, busy: bool) -> None:
        now = time.monotonic()
        self._seq += 1
        if self._starved_since is not None:
            self.starved_s += now - self._starved_since
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
        """{"clock_s", "phases": {name: {"s", "n"}}, "starved_s"}, all
        cumulative; ``clock_s`` is ``time.monotonic()`` now, so that the
        difference of two snapshots carries its own denominator. Spans and
        a starved stretch still open count up to now (a decode wait lasts a
        quarter of a second: left out, two snapshots five seconds apart
        would not add up); ``n`` counts closed spans."""
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
        now = time.monotonic()
        phases = {k: {"s": v[0], "n": v[1]}
                  for k, v in list(self.phases.items())}
        inner_t0 = now
        for span in reversed(list(self._stack)):
            cell = phases.setdefault(span._name, {"s": 0.0, "n": 0})
            cell["s"] += inner_t0 - span._t0 - span._children
            inner_t0 = span._t0
        since = self._starved_since
        return {"clock_s": now, "phases": phases,
                "starved_s": self.starved_s
                + (now - since if since is not None else 0.0)}


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
