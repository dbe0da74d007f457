"""Lightweight nested span tracer with Chrome trace-event export.

One span path, three sinks.  A ``Span`` is name, category, start,
duration, thread, span id, parent id; every span that ends goes to

- its ``Tracer``: one per statement (owned by its ``QueryObs``), or the
  process-wide ``PROCESS`` tracer for work no one statement owns (a wire
  command, a batch round and its legs, a sampler's tick, a replica
  preparation outside a statement).  ``TRACE <stmt>``, ``/debug/trace``
  and the slow log read these;
- the profiler's clock: while a ``jax.profiler`` session is open a live
  span is also a ``TraceAnnotation("tinysql/<name>", span=, parent=)``
  on the thread that runs it, so the ``.xplane.pb``'s ``/host:CPU``
  plane holds the program's spans on the device plane's time axis.
  With no session open that is one flag test.  The annotation class is
  bound by ``ops/kernels.jax()`` (``bind_profiler``): this package
  imports without jax;
- the totals table ``name -> {count, sum_s, self_s, max_s}``
  (``totals()``), where ``self_s`` is the duration less the child spans
  that ran on the same thread: what the benchmark reads.  Written only
  from this module (qlint OB408).

Cheap enough to leave always-on: a statement records a handful of
lifecycle spans (parse → plan → place → execute) plus one span per
program dispatch / D2H drain / compile-cache miss / pipeline stage
block.

Cross-thread parenting: the devpipe producer thread and the statement
pool's workers run inside a ``contextvars`` copy of the creator's
context, so a span's parent is whatever span was live when the copy was
taken, even though it executes on another thread.  Chrome's viewer lanes
by ``tid``; our own JSON keeps explicit ``parent`` ids so tests (and
tools/trace2json.py) can verify the nesting.

A process-global ring buffer keeps the last N query traces for the
status server's ``/debug/trace`` endpoint (``TINYSQL_TRACE_RING`` caps
N, default 32); the ``PROCESS`` tracer keeps the last
``PROCESS_SPANS_PER_TRACE`` x N process spans beside them.
"""
from __future__ import annotations

import contextlib
import contextvars
import gc
import itertools
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional

_ids = itertools.count(1)


#: ``jax.profiler.TraceAnnotation`` once ``ops/kernels.jax()`` has
#: loaded jax; None before (and in a process that never loads it)
_annotation = None


def bind_profiler(annotation) -> None:
    """Called where jax is first loaded: from here on a live span is
    also an annotation of the profiler whenever a session is open."""
    global _annotation
    _annotation = annotation


def _annotate(name: str, **stats):
    """The entered annotation, or None where no profiler session is
    open (``is_enabled`` is the flag test a ``TraceMe`` makes)."""
    ann = _annotation
    if ann is None or not ann.is_enabled():
        return None
    live = ann("tinysql/" + name, **stats)
    live.__enter__()
    return live


@contextlib.contextmanager
def stretch(span: "Span"):
    """One stretch of a held span (one begun with ``annotate=False``)
    that this thread runs whole: annotated on the profiler's clock under
    the span's own id, as a live span is (an event loop's command, in
    the ticks that do its work).  With no session open, one flag test."""
    ann = _annotate(span.name, span=span.sid, parent=span.parent or 0)
    try:
        yield span
    finally:
        if ann is not None:
            ann.__exit__(None, None, None)


#: the live span of this context: the parent of the next one begun in
#: it.  A context copied to another thread (the devpipe producer, a pool
#: worker running an entry) carries it along
CURRENT_SPAN: contextvars.ContextVar = contextvars.ContextVar(
    "tinysql_obs_span", default=None)


class Span:
    """One span, and the context manager that runs it: entering makes it
    the live span of the context, leaving ends it on its tracer.  The
    hot path takes no lock and allocates little: with the heap profiler
    on (a server's default) every allocation is traced, and twenty
    threads ending spans under one lock queue for the GIL behind it."""
    __slots__ = ("sid", "name", "cat", "start_s", "dur_s", "tid",
                 "tname", "parent", "args", "up", "child_s", "ann",
                 "tracer", "tok")

    def __init__(self, name: str, cat: str, up: Optional["Span"],
                 args: Optional[dict] = None,
                 tracer: Optional["Tracer"] = None):
        self.sid = next(_ids)
        self.name = name
        self.cat = cat
        self.start_s = time.perf_counter()
        self.dur_s = 0.0
        # the recording thread's NAME rides along so TRACE <stmt> and
        # offline tooling can classify the span by serving role
        # (obs/conprof.classify) without a live thread table
        th = threading.current_thread()
        self.tid = th.ident
        self.tname = th.name
        #: the span that caused this one, until this one ends
        self.up = up
        self.parent = up.sid if up is not None else None
        self.args = args if args is not None else {}
        #: seconds of ended child spans that ran on this span's thread
        self.child_s = 0.0
        self.ann = None
        #: where it ends, until it has (no cycle is left behind)
        self.tracer = tracer
        self.tok = None

    def __enter__(self) -> "Span":
        self.tok = CURRENT_SPAN.set(self)
        return self

    def __exit__(self, *exc) -> bool:
        CURRENT_SPAN.reset(self.tok)
        self.tok = None
        self.tracer.end(self)
        return False

    @property
    def end_s(self) -> float:
        """Where it ended, on ``perf_counter``'s clock (once it has)."""
        return self.start_s + self.dur_s

    def to_dict(self) -> dict:
        return {"id": self.sid, "name": self.name, "cat": self.cat,
                "ts_us": round(self.start_s * 1e6, 1),
                "dur_us": round(self.dur_s * 1e6, 1),
                "tid": self.tid, "thread": self.tname,
                "parent": self.parent, "args": self.args}


# ---- totals: what the benchmark reads --------------------------------------

#: thread ident -> {name -> [count, sum_s, self_s, max_s]}: each thread
#: adds to a table of its own (a reused ident carries on in the old
#: one), so the hot path needs no lock; ``totals()`` sums them
_TOTALS: Dict[int, Dict[str, list]] = {}
#: the collector's own [count, sum_s, max_s]: its callback may run while
#: this thread holds any lock of this module, so it takes none (one
#: collection runs at a time)
_GC = [0, 0.0, 0.0]


def _row(tid: int, name: str) -> list:
    """The row of ``name`` in the table of the thread ``tid``, which
    only that thread writes."""
    mine = _TOTALS.get(tid)
    if mine is None:
        # qlint: disable=CC701 -- one GIL-atomic insert of the thread's own table under its own ident; no other thread writes that table
        mine = _TOTALS.setdefault(tid, {})
    t = mine.get(name)
    if t is None:
        t = mine[name] = [0, 0.0, 0.0, 0.0]
    return t


def _count(tid: int, name: str, dur_s: float, self_s: float) -> None:
    """On the thread ``tid`` only."""
    t = _row(tid, name)
    t[0] += 1
    t[1] += dur_s
    t[2] += self_s
    if dur_s > t[3]:
        t[3] = dur_s


def count(name: str, n: int = 1) -> None:
    """A counter in the totals table: ``n`` more of ``name``, no time
    (the event loop's ``aio_*`` counts).  On the calling thread's table,
    as a span's end is."""
    _row(threading.get_ident(), name)[0] += n


def totals() -> Dict[str, dict]:
    """A copy of the table: every span that has ended since the process
    began, by name, the counters ``count`` adds (a count and no time),
    plus jax's own program-load phases (``jax.trace``, ``jax.lower``,
    ``jax.backend_compile``) and the collector (``gc``)."""
    rows: Dict[str, list] = {}
    for mine in list(_TOTALS.values()):
        for name, t in list(mine.items()):
            n, sum_s, self_s, max_s = t
            row = rows.get(name)
            if row is None:
                rows[name] = [n, sum_s, self_s, max_s]
            else:
                row[0] += n
                row[1] += sum_s
                row[2] += self_s
                row[3] = max(row[3], max_s)
    out = {name: {"count": t[0], "sum_s": t[1], "self_s": t[2],
                  "max_s": t[3]} for name, t in rows.items()}
    # the collector's row last: a collection that this copy's own
    # allocations set off is in it
    n, sum_s, max_s = _GC
    if n:
        out["gc"] = {"count": n, "sum_s": sum_s, "self_s": sum_s,
                     "max_s": max_s}
    return out


def program_load_s() -> float:
    """Seconds jax has spent tracing, lowering and compiling programs
    (or loading them from its cache) since the process began: the self
    times of its three phases.  It rises when a phase ENDS, where
    ``progcache``'s build wall rises before a jitted function's first
    call has compiled anything."""
    rows = totals()
    return sum(rows[name]["self_s"] for name in JAX_PHASES.values()
               if name in rows)


class Tracer:
    """Span sink for one statement, or (``keep`` given) the bounded one
    of the process.  Append-only; the devpipe producer thread and the
    consumer record concurrently, and an ``append`` (a list's or a
    deque's) and a ``list()`` of either are each one step under the
    interpreter's lock."""

    def __init__(self, keep: Optional[int] = None):
        self._spans = [] if keep is None else deque(maxlen=keep)

    def begin(self, name: str, cat: str = "query",
              up: Optional[Span] = None, args: Optional[dict] = None,
              annotate: bool = True) -> Span:
        """``annotate=False``: a span that no one thread runs from start
        to end (an event loop's command) cannot nest on a thread's
        profiler stack."""
        s = Span(name, cat, up, args, self)
        if annotate:
            s.ann = _annotate(name, span=s.sid, parent=s.parent or 0)
        return s

    def end(self, span: Span) -> None:
        ann, span.ann = span.ann, None
        if ann is not None:
            if span.args:
                ann.set_metadata(**{k: str(v)
                                    for k, v in span.args.items()})
            ann.__exit__(None, None, None)
        dur = span.dur_s = time.perf_counter() - span.start_s
        up, span.up, span.tracer = span.up, None, None
        if up is not None and up.tid == span.tid:
            up.child_s += dur
        _count(span.tid, span.name, dur, dur - span.child_s)
        self._spans.append(span)

    def add_complete(self, name: str, start_s: float, dur_s: float,
                     cat: str = "query", up: Optional[Span] = None,
                     args: Optional[dict] = None) -> Span:
        """Record an already-measured interval (e.g. the batch parse wall
        measured before the statement scope existed).  It was never
        live: no annotation, and its parent's self time keeps it."""
        s = Span(name, cat, up, args)
        s.up = None
        s.start_s = start_s
        s.dur_s = dur_s
        _count(s.tid, name, dur_s, dur_s)
        self._spans.append(s)
        return s

    def adopt(self, other: "Tracer") -> None:
        """Take over the ended spans of a tracer that nothing will
        publish (a parked collect leg's, a round dispatch's capture)."""
        self._spans.extend(list(other._spans))

    def spans(self) -> List[dict]:
        return [s.to_dict() for s in list(self._spans)]

    def ended(self) -> List[Span]:
        """The ended spans themselves: what ``publish_trace`` keeps, so
        that a statement pays no rendering for a trace nobody reads."""
        return list(self._spans)

    def chrome_trace(self, pid: int = 0,
                     label: str = "") -> Dict[str, list]:
        """chrome://tracing / Perfetto ``traceEvents`` JSON (via the
        shared ``spans_to_events`` converter)."""
        out = {"traceEvents": spans_to_events(self.spans(), pid=pid)}
        if label:
            out["otherData"] = {"query": label}
        return out


def spans_to_events(spans: List[dict], pid: int = 0,
                    label: str = "") -> List[dict]:
    """THE span-dict -> Chrome-trace-event conversion, shared by
    ``Tracer.chrome_trace`` and tools/trace2json.py so the two export
    surfaces cannot drift.  Spans become phase-``X`` complete events;
    thread lanes come from the recording thread's ident and carry its
    recorded name; ``label`` (when given) names the process track."""
    events: List[dict] = []
    tids: Dict[int, int] = {}
    names: Dict[int, str] = {}
    for sp in spans:
        lane = tids.setdefault(sp.get("tid", 0), len(tids))
        names.setdefault(lane, str(sp.get("thread") or "main"))
    if label:
        events.append({"ph": "M", "pid": pid, "tid": 0,
                       "name": "process_name", "args": {"name": label}})
    for tid, lane in tids.items():
        events.append({"ph": "M", "pid": pid, "tid": lane,
                       "name": "thread_name",
                       "args": {"name": names[lane]}})
    for sp in spans:
        events.append({
            "ph": "X", "pid": pid, "tid": tids[sp.get("tid", 0)],
            "name": sp.get("name", "?"), "cat": sp.get("cat", "query"),
            "ts": sp.get("ts_us", 0.0), "dur": sp.get("dur_us", 0.0),
            "args": dict(sp.get("args") or {}, span_id=sp.get("id"),
                         parent=sp.get("parent")),
        })
    return events


# ---- TRACE <stmt> rendering -----------------------------------------------

#: TRACE <stmt> result columns (session/_exec_trace)
TRACE_COLUMNS = ("span", "parent", "start_offset_us", "duration_us",
                 "thread_role")


def trace_rows(spans: List[dict]) -> List[list]:
    """Render recorded span dicts as the ``TRACE <stmt>`` resultset:
    depth-indented span name (tree order: children by start time under
    their parent), parent span name, start offset relative to the
    earliest span (µs), duration (µs), and the recording thread's
    serving role (obs/conprof.classify over the captured thread name —
    a devpipe stage span reads ``devpipe`` even though it parents into
    the statement's chain)."""
    from .conprof import classify
    if not spans:
        return []
    by_id = {sp["id"]: sp for sp in spans}
    children: Dict[Optional[int], List[dict]] = {}
    for sp in spans:
        parent = sp.get("parent")
        if parent is not None and parent not in by_id:
            parent = None  # parent never ended (e.g. the outer execute)
        children.setdefault(parent, []).append(sp)
    for sibs in children.values():
        sibs.sort(key=lambda s: s.get("ts_us", 0.0))
    t0 = min(sp.get("ts_us", 0.0) for sp in spans)
    out: List[list] = []

    def render(sp: dict, depth: int) -> None:
        parent = by_id.get(sp.get("parent"))
        pname = ""
        if parent is not None:
            pname = str(parent.get("name", ""))
        out.append(["  " * depth + str(sp.get("name", "?")),
                    pname,
                    round(sp.get("ts_us", 0.0) - t0, 1),
                    round(sp.get("dur_us", 0.0), 1),
                    classify(str(sp.get("thread", "")))])
        for child in children.get(sp["id"], []):
            render(child, depth + 1)

    for root in children.get(None, []):
        render(root, 0)
    return out


# ---- process-global ring of recent query traces (/debug/trace) ----------

def _ring_cap() -> int:
    try:
        return max(1, int(os.environ.get("TINYSQL_TRACE_RING", "32")))
    except ValueError:
        return 32


_ring_mu = threading.Lock()
_RING: deque = deque(maxlen=_ring_cap())


def publish_trace(entry: dict) -> None:
    """Append one finished statement's trace record:
    ``{"sql", "ts", "total_ms", "error", "spans"}``.  ``spans`` may be
    the ended ``Span`` objects (``Tracer.ended``): they are rendered to
    dicts when the ring is read, once, and most traces leave the ring
    unread."""
    with _ring_mu:
        _RING.append(entry)


def recent_traces(n: Optional[int] = None) -> List[dict]:
    with _ring_mu:
        out = list(_RING)
    out = out[-n:] if n else out
    for entry in out:
        spans = entry["spans"]
        if spans and isinstance(spans[0], Span):
            entry["spans"] = [s.to_dict() for s in spans]
    return out


def ring_len() -> int:
    return len(_RING)


def clear_traces() -> None:
    with _ring_mu:
        _RING.clear()


# ---- the process's own spans ----------------------------------------------

#: process spans kept for each statement trace the ring keeps: a wire
#: statement leaves nine (``wire.idle``, ``wire.command``, ``wire.parse``,
#: ``pool.wait``, ``pool.submit``, ``pool.wake``, ``wire.write``,
#: ``solo``, ``stmt.finish``), a round member its share of the legs
PROCESS_SPANS_PER_TRACE = 12

#: the sink of spans that no statement owns
PROCESS = Tracer(keep=PROCESS_SPANS_PER_TRACE * _ring_cap())


def process_trace() -> Optional[dict]:
    """The process spans still kept, as one more ``/debug/trace`` entry
    (same shape as a statement's, so tools/trace2json.py reads it)."""
    spans = PROCESS.spans()
    if not spans:
        return None
    return {"sql": "(process)", "ts": time.time(), "total_ms": 0.0,
            "error": False, "spans": spans}


# ---- the collector -----------------------------------------------------------

#: collections at least this long also become a process span
GC_SPAN_MIN_S = 1e-3
_gc_live = [0.0, None]  # start, annotation: one collection at a time


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook.  Every collection counts in the totals;
    a long one leaves a span too.  While a profiler session is open
    each one is annotated: an annotation is entered at the start, before
    its length is known.  Lock-free (see ``_GC``)."""
    if phase == "start":
        _gc_live[0] = time.perf_counter()
        _gc_live[1] = _annotate("gc", generation=info.get("generation", -1))
        return
    ann, _gc_live[1] = _gc_live[1], None
    if ann is not None:
        ann.__exit__(None, None, None)
    start = _gc_live[0]
    dur = time.perf_counter() - start
    _GC[0] += 1
    _GC[1] += dur
    if dur > _GC[2]:
        _GC[2] = dur
    if dur >= GC_SPAN_MIN_S:
        s = Span("gc", "background", None,
                 {"generation": info.get("generation", -1),
                  "collected": info.get("collected", 0)})
        s.start_s, s.dur_s = start, dur
        PROCESS._spans.append(s)


def watch_collector() -> None:
    """Idempotent; the server calls it as it starts."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


# ---- jax's own program-load phases ---------------------------------------------

#: jax.monitoring duration events -> the names they count under.  The
#: ``compile`` span sees only ``progcache.get``'s build; a jitted
#: function traces, lowers and loads on its first *call*
JAX_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.backend_compile",
}
_jax_tls = threading.local()
#: ended phases a thread remembers, to find the ones a later, longer
#: phase covered
_JAX_REMEMBERED = 64


def on_jax_duration(event: str, seconds: float, **_kw) -> None:
    """The one ``jax.monitoring`` duration listener (registered where
    ``ops/kernels.jax()`` loads jax).  jax reports a phase when it ends;
    a phase that began before phases this thread has already reported
    covered them (tracing a program traces the jitted functions it
    calls), so its self time is less theirs: the self times sum to the
    seconds this thread spent loading programs."""
    name = JAX_PHASES.get(event)
    if name is None:
        return
    now = time.perf_counter()
    start = now - seconds
    ended = getattr(_jax_tls, "ended", None)
    if ended is None:
        ended = _jax_tls.ended = []
    covered = 0.0
    while ended and ended[-1][0] >= start:
        covered += ended.pop()[1]
    ended.append((start, seconds))
    del ended[:-_JAX_REMEMBERED]
    _count(threading.get_ident(), name, seconds,
           max(seconds - covered, 0.0))
