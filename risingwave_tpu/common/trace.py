"""Trace-lite: epoch-scoped distributed tracing flight recorder.

Reference counterpart: ``await-tree`` / the embedded tracing RisingWave
ships for barrier attribution (``src/common/src/util/epoch.rs`` epochs
plus the meta dashboard's per-actor traces), scaled down to the same
stdlib-only discipline as ``common/metrics.py``: every process keeps a
bounded ring buffer of completed spans (a *flight recorder* — old
spans fall off, nothing blocks, nothing is ever written on the hot
path unless tracing is on), and the meta assembles the cluster-wide
view on demand by pulling each peer's buffer over ``rpc_trace_dump``.

Model
-----
- A **trace** is one cluster round: ``trace_id = "round-<N>"`` where
  ``N`` is the global cluster epoch the round commits.  The meta opens
  the root span; the context ``(trace_id, span_id)`` rides RPC frames
  (a top-level ``"trace"`` key, outside ``params``) so worker/uploader
  /serving spans parent correctly across processes.
- A **span** is a finished interval: dict with ``trace_id``,
  ``span_id`` (``"<role>:<n>"`` — unique cluster-wide without
  coordination), ``parent_id``, ``role``, ``name``, ``ts`` (wall
  seconds), ``t_mono`` (``time.monotonic()`` at entry: on Linux one
  clock for every process of a host, the one a client stamps its
  requests with), ``dur`` (seconds), ``attrs``, ``thread``.  Only
  completed spans enter the ring: a SIGKILL loses at most the spans
  in flight, and the survivors still parse (satellite:
  truncated-but-parseable).
- The **served single node** roots its own traces: one tree a barrier
  (``tick-<n>``), a pgwire statement (``read-<n>``) and a scrape
  (``scrape-<n>``), opened with ``root()``.
- **Every span is a counter**: a finished span adds its seconds and 1
  to ``trace_span_seconds_total{span[,job]}`` /
  ``trace_span_total{span[,job]}`` of a metrics registry (``job``
  where the span carries that attribute, so ``DROP`` retires them with
  the job's other series).  Which registry: the one the span was
  opened with (``metrics=``), else its parent's on this thread, else
  the one ``configure(metrics=...)`` named — a process may hold many
  engines, and an engine's spans never land in another's registry.
- **One clock with the device trace**: where ``configure(annotate=
  ...)`` handed in ``jax.profiler.TraceAnnotation`` (the roles that
  hold a chip), a span is also an annotation on the profiler's host
  plane, beside the device's operations.  This module never imports
  jax itself: ``--role serving`` boots without it.
- **Overhead contract**: ``sample_n == 0`` disables tracing — `span()`
  returns a module-level null singleton (zero allocations, no clock
  reads) and ``sampled_span()`` likewise.  ``sample_n >= 1`` records
  every control-plane span (rounds are low-rate) and 1-in-N
  data-plane spans (serving reads, compact/scrub cycles).  Nothing in
  here touches jax or a device: timing is wall-clock only, so a span
  around a dispatch measures the host-side call, never forces a sync.
- **Determinism under retries**: spans are recorded where the work
  runs.  A round-tagged barrier retry that answers from the worker's
  round cache re-runs no chunks and records no spans — one span tree
  per round by construction (the meta-side barrier-unit span carries
  an ``attempts`` attr instead).
"""

from __future__ import annotations

import itertools
import threading
import time


class _NullSpan:
    """Tracing disabled / unsampled: a shared, allocation-free no-op.
    Also what ``span()`` hands out mid-tree when the recorder is off,
    so call sites never branch."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> "_NullSpan":
        return self

    def drop(self) -> None:
        pass

    @property
    def ctx(self):
        return None


NULL_SPAN = _NullSpan()


class _Span:
    """One in-flight span; records itself into the ring on exit."""

    __slots__ = ("_rec", "trace_id", "span_id", "parent_id", "name",
                 "attrs", "_sink", "_ann", "_t0", "_ts", "_mono",
                 "_pushed", "_dropped")

    def __init__(self, rec, trace_id, span_id, parent_id, name, attrs,
                 sink=None):
        self._rec = rec
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.attrs = attrs
        #: the metrics registry this span (and its children on this
        #: thread) count into
        self._sink = sink
        self._ann = None
        self._t0 = 0.0
        self._ts = 0.0
        self._mono = 0.0
        self._pushed = False
        self._dropped = False

    @property
    def ctx(self) -> tuple:
        """The (trace_id, span_id) pair to hand to children — RPC
        frames, cross-thread closures, UploadTask fields."""
        return (self.trace_id, self.span_id)

    def set(self, **attrs) -> "_Span":
        self.attrs.update(attrs)
        return self

    def drop(self) -> None:
        """Leave no record on exit: an idle poll that found nothing to
        do is not an interval worth a ring entry."""
        self._dropped = True

    def __enter__(self) -> "_Span":
        annotate = self._rec._annotate
        if annotate is not None:
            self._ann = annotate(self.name)
            self._ann.__enter__()
        self._ts = time.time()
        self._mono = time.monotonic()
        self._t0 = time.perf_counter()
        self._rec._stack().append(
            (self.trace_id, self.span_id, self._sink, self))
        self._pushed = True
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        if self._pushed:
            stack = self._rec._stack()
            if stack and stack[-1][1] == self.span_id:
                stack.pop()
            self._pushed = False
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        if not self._dropped:
            self._rec._record(self, dur)
        return False


class SpanRecorder:
    """Per-process bounded span ring + thread-local trace context."""

    def __init__(self, role: str = "proc", sample_n: int = 1,
                 capacity: int = 4096):
        self.role = role
        self.sample_n = sample_n
        self.capacity = capacity
        self._lock = threading.Lock()
        self._ring: list = []
        self._head = 0
        self._ids = itertools.count(1)
        self._roots = itertools.count(1)
        self._sample_ctr = itertools.count()
        self._tls = threading.local()
        #: ``name -> context manager`` (``jax.profiler.TraceAnnotation``
        #: where the role holds a chip), or None
        self._annotate = None
        #: where a span with no registry of its own counts, or None
        self._metrics = None

    def configure(self, role: str | None = None,
                  sample_n: int | None = None,
                  capacity: int | None = None,
                  annotate=None, metrics=None) -> "SpanRecorder":
        if role is not None:
            self.role = role
        if sample_n is not None:
            self.sample_n = sample_n
        if annotate is not None:
            self._annotate = annotate
        if metrics is not None:
            self._metrics = metrics
        if capacity is not None and capacity != self.capacity:
            with self._lock:
                self.capacity = capacity
                self._ring = self._snapshot_locked()[-capacity:]
                self._head = 0
        return self

    @property
    def enabled(self) -> bool:
        return self.sample_n > 0

    # -- context ---------------------------------------------------------
    def _stack(self) -> list:
        s = getattr(self._tls, "stack", None)
        if s is None:
            s = self._tls.stack = []
        return s

    def current(self) -> tuple | None:
        """The active (trace_id, span_id) on THIS thread, or None."""
        s = getattr(self._tls, "stack", None)
        return s[-1][:2] if s else None

    def activate(self, ctx) -> "_CtxGuard | _NullSpan":
        """Adopt a remote context (an RPC frame's ``trace`` key) for
        the current thread.  No span is recorded — children attach."""
        if not self.enabled or not ctx:
            return NULL_SPAN
        return _CtxGuard(self, (ctx[0], ctx[1], None, None))

    # -- span creation ---------------------------------------------------
    def span(self, name: str, ctx: tuple | None = None,
             trace_id: str | None = None, metrics=None, **attrs):
        """Open a control-plane span.  Parent resolution: explicit
        ``ctx`` (cross-thread/cross-process) > the thread's active
        span > root (``trace_id`` names a fresh trace).  ``metrics``
        names the registry this span and its children on this thread
        count into (default: the parent's)."""
        if self.sample_n <= 0:
            return NULL_SPAN
        s = getattr(self._tls, "stack", None)
        if ctx is not None:
            tid, parent = ctx[0], ctx[1]
        elif s:
            tid, parent, inherited, _ = s[-1]
            if metrics is None:
                metrics = inherited
        elif trace_id is not None:
            tid, parent = trace_id, None
        else:
            return NULL_SPAN  # no trace active: nothing to attach to
        if trace_id is not None:
            tid = trace_id
        span_id = f"{self.role}:{next(self._ids)}"
        return _Span(self, tid, span_id, parent, name, attrs, metrics)

    def root(self, trace: str, name: str, metrics=None, **attrs):
        """Open the root of a fresh trace ``<trace>-<n>`` (the single
        node's ``tick`` / ``read`` / ``scrape``), ``n`` counted by
        this process.  Where a trace is already active on this thread
        no second root opens: the caller joins the active span (the
        served node's barrier loop opens ``tick`` before it takes the
        engine lock, and ``Engine.tick`` under it adds none), and
        attributes it sets land there."""
        if self.sample_n <= 0:
            return NULL_SPAN
        s = getattr(self._tls, "stack", None)
        if s:
            return _Joined(s[-1][3], attrs)
        return self.span(name, trace_id=f"{trace}-{next(self._roots)}",
                         metrics=metrics, **attrs)

    def held(self, lock, name: str) -> "_Held":
        """``with rec.held(lock, "tick.lock_wait"):`` — take ``lock``
        inside a span of that name (the wait), hold it for the body."""
        return _Held(self, lock, name)

    def sampled_span(self, name: str, trace_id: str | None = None,
                     ctx: tuple | None = None, **attrs):
        """Data-plane span recorded 1-in-``sample_n`` (serving reads,
        compaction/scrub cycles).  Off or unsampled = the null span.
        ``ctx`` parents the sampled span into an existing trace (a
        serving replica tags reads with the last committed round's
        root ctx); otherwise it roots a ``sampled-<role>`` trace."""
        n = self.sample_n
        if n <= 0:
            return NULL_SPAN
        if next(self._sample_ctr) % n:
            return NULL_SPAN
        if ctx is not None:
            return self.span(name, ctx=ctx, **attrs)
        tid = trace_id if trace_id is not None \
            else f"sampled-{self.role}"
        return self.span(name, trace_id=tid, **attrs)

    # -- the ring --------------------------------------------------------
    def _record(self, span: _Span, dur: float) -> None:
        entry = {
            "trace_id": span.trace_id,
            "span_id": span.span_id,
            "parent_id": span.parent_id,
            "role": self.role,
            "name": span.name,
            "ts": span._ts,
            "t_mono": span._mono,
            "dur": dur,
            "attrs": span.attrs,
            "thread": threading.current_thread().name,
        }
        with self._lock:
            if len(self._ring) < self.capacity:
                self._ring.append(entry)
            else:
                self._ring[self._head] = entry
                self._head = (self._head + 1) % self.capacity
        sink = span._sink if span._sink is not None else self._metrics
        if sink is not None:
            job = span.attrs.get("job")
            labels = {"span": span.name} if job is None \
                else {"span": span.name, "job": job}
            sink.inc("trace_span_seconds_total", dur, **labels)
            sink.inc("trace_span_total", 1.0, **labels)

    def _snapshot_locked(self) -> list:
        return self._ring[self._head:] + self._ring[:self._head]

    def dump(self, trace_id: str | None = None) -> list[dict]:
        """Snapshot the ring, oldest first (the ``rpc_trace_dump``
        payload — plain dicts, JSON-clean)."""
        with self._lock:
            spans = self._snapshot_locked()
        if trace_id is not None:
            spans = [s for s in spans if s["trace_id"] == trace_id]
        return spans

    def clear(self) -> None:
        with self._lock:
            self._ring = []
            self._head = 0


class _Joined:
    """``root()`` inside an active trace: no span of its own, the
    active one takes the attributes (none where the context was
    adopted from another process)."""

    __slots__ = ("_span",)

    def __init__(self, span, attrs):
        self._span = span
        self.set(**attrs)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> "_Joined":
        if self._span is not None:
            self._span.attrs.update(attrs)
        return self

    @property
    def ctx(self):
        return None if self._span is None else self._span.ctx


class _Held:
    __slots__ = ("_rec", "_lock", "_name")

    def __init__(self, rec, lock, name):
        self._rec, self._lock, self._name = rec, lock, name

    def __enter__(self):
        with self._rec.span(self._name):
            self._lock.acquire()
        return self

    def __exit__(self, *exc):
        self._lock.release()
        return False


class _CtxGuard:
    __slots__ = ("_rec", "_ctx", "_pushed")

    def __init__(self, rec: SpanRecorder, ctx: tuple):
        self._rec = rec
        self._ctx = ctx
        self._pushed = False

    def __enter__(self):
        self._rec._stack().append(self._ctx)
        self._pushed = True
        return self

    def __exit__(self, *exc):
        if self._pushed:
            stack = self._rec._stack()
            if stack and stack[-1] is self._ctx:
                stack.pop()
            self._pushed = False
        return False


# ---------------------------------------------------------------------------
# assembly (meta-side / ctl-side): merge per-process dumps into round
# trees and export Chrome trace_event JSON


def merge_dumps(dumps: list[list[dict]]) -> list[dict]:
    """Concatenate per-process dumps, dedup by span_id (a dump pulled
    twice must not double spans), order by start time."""
    seen: set[str] = set()
    out: list[dict] = []
    for d in dumps:
        for s in d or ():
            sid = s.get("span_id")
            if sid in seen:
                continue
            seen.add(sid)
            out.append(s)
    out.sort(key=lambda s: s.get("ts", 0.0))
    return out


def round_ids(spans: list[dict]) -> list[int]:
    """The committed-round numbers present in a merged dump."""
    out = set()
    for s in spans:
        t = s.get("trace_id", "")
        if t.startswith("round-"):
            try:
                out.add(int(t[len("round-"):]))
            except ValueError:
                pass
    return sorted(out)


def spans_for_round(spans: list[dict], round_no: int) -> list[dict]:
    want = f"round-{round_no}"
    return [s for s in spans if s.get("trace_id") == want]


def tree_check(spans: list[dict]) -> dict:
    """Structural audit of one trace's spans: exactly one root, every
    parent resolvable, and the root's interval covers every child.
    Truncated dumps (dead worker, ring wrap) stay *parseable*: orphan
    spans are reported, not fatal."""
    by_id = {s["span_id"]: s for s in spans}
    roots = [s for s in spans if s.get("parent_id") is None]
    orphans = [s for s in spans
               if s.get("parent_id") is not None
               and s["parent_id"] not in by_id]
    covered = True
    if len(roots) == 1:
        r = roots[0]
        r0, r1 = r["ts"], r["ts"] + r["dur"]
        slack = 0.25  # wall clocks across processes wobble
        # coverage applies to the BARRIER PATH only: checkpoint
        # uploads are async by contract and sampled serving reads
        # attach to an already-committed round — both legitimately
        # outlive the root span (with their ``.fetch`` / ``.encode``
        # / ... children)
        async_ok = {"ckpt_prepare", "ckpt_commit", "serving_read"}
        for s in spans:
            if s is r or s["name"].split(".")[0] in async_ok:
                continue
            if s["ts"] < r0 - slack or s["ts"] + s["dur"] > r1 + slack:
                covered = False
    return {
        "spans": len(spans),
        "roots": [s["span_id"] for s in roots],
        "orphans": [s["span_id"] for s in orphans],
        "complete": len(roots) == 1 and not orphans,
        "root_covers": covered,
        "roles": sorted({s["role"] for s in spans}),
        "names": sorted({s["name"] for s in spans}),
    }


def to_chrome_trace(spans: list[dict]) -> dict:
    """Chrome ``trace_event`` JSON (object format) for
    chrome://tracing / Perfetto: one pid per role, one tid per
    (role, thread), complete ``"X"`` events in microseconds."""
    events: list[dict] = []
    pids: dict[str, int] = {}
    tids: dict[tuple, int] = {}
    for s in spans:
        role = s.get("role", "?")
        if role not in pids:
            pids[role] = len(pids) + 1
            events.append({
                "ph": "M", "name": "process_name", "pid": pids[role],
                "tid": 0, "args": {"name": role},
            })
        tkey = (role, s.get("thread", ""))
        if tkey not in tids:
            tids[tkey] = len(tids) + 1
            events.append({
                "ph": "M", "name": "thread_name", "pid": pids[role],
                "tid": tids[tkey], "args": {"name": tkey[1] or "main"},
            })
        args = dict(s.get("attrs") or {})
        args["span_id"] = s["span_id"]
        if s.get("parent_id"):
            args["parent_id"] = s["parent_id"]
        args["trace_id"] = s.get("trace_id")
        events.append({
            "ph": "X",
            "name": s["name"],
            "cat": s.get("trace_id", "trace"),
            "pid": pids[role],
            "tid": tids[tkey],
            "ts": s["ts"] * 1e6,
            "dur": max(s["dur"], 0.0) * 1e6,
            "args": args,
        })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


#: process-wide recorder (mirrors GLOBAL_METRICS) — the server wires
#: role + sample_n at boot; library code just imports and records
GLOBAL_TRACE = SpanRecorder()
