"""Flight-recorder tracing for the FL round pipeline (paper §3.3: the
dashboard's "where did this round spend its time" question, answered at
production depth instead of ad-hoc log strings).

Three instruments, stdlib-only so ``repro.core`` modules can import it
without dependency cycles:

``span``/``Tracer``
    A span-based tracer: ``with tracing.span("snapshot", task=3):``
    opens a timed span (monotonic wall clock via ``perf_counter`` + CPU
    clock via ``process_time``) that nests under whatever span is open on
    the SAME thread — the per-thread stack makes the service -> local
    training -> privacy chain tree fall out of the call structure with no
    plumbing. Finished top-level spans collect on the tracer
    (lock-protected; safe with the simulator's threads) and export as
    Chrome/Perfetto ``trace_events`` JSON (:meth:`Tracer.to_perfetto`).

    ``Tracer(profiler=True)`` also mirrors every span into the JAX
    profiler: while open, a span is a ``jax.profiler.TraceAnnotation``
    named ``florida.<span name>``, so a profiler trace holds the program's
    stages and the device's operations on one clock. JAX is imported only
    in that mode; this module stays importable without it.

    A span around a jitted call times the HOST side: the dispatch, plus
    whatever sync falls inside it. Device time lives in the profiler
    trace's module and op events; stages fused into one program
    (DP/quantize/mask/VG-sum in ``privacy_engine``) are named there by
    ``jax.named_scope``, not by host spans.

    Spans that move data between host and device carry ``h2d_bytes`` /
    ``d2h_bytes`` attributes (:meth:`Span.transfer`, at the site of the
    transfer: a host array handed to a jitted call or ``jnp.asarray`` is
    h2d, a device array pulled by ``np.asarray``/``device_get`` is d2h).

    The default tracer is a :class:`NullTracer` whose ``span()`` returns a
    shared no-op context manager — library callers pay one dict build and
    one method call per span site, and ``transfer`` counts nothing.

``FlightRecorder``
    A per-task JSONL round transcript: every closed round appends one
    structured event (cohort ids, survivors, stage timings lifted from
    the round's span subtree, ``stage2_route``, ``n_shards``, void
    reason). Self-sufficient for post-hoc inspection: ``florida trace
    <task>`` renders transcripts, and ``perfetto_from_flight`` rebuilds a
    Perfetto timeline from the recorded stage offsets alone.

``jit_cache_sizes``
    The ``jit_cache_misses`` probe: sums ``_cache_size()`` over the
    repo's shared jitted entry points (module-level table + dynamically
    ``register_jit``-ed per-instance executables, e.g. a CohortEngine's
    vmapped cohort fn). A fixed-shape contract regression (async batch
    pad classes, streaming-wave width) shows up as a nonzero per-round
    delta — testable, not just benchmarkable.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time
import weakref
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any

# ----------------------------------------------------------------------
# spans
# ----------------------------------------------------------------------


PROFILER_PREFIX = "florida."


@dataclass
class Span:
    """One timed region. ``t0``/``t1`` are ``perf_counter`` seconds
    (monotonic wall), ``cpu0``/``cpu1`` ``process_time`` seconds."""
    name: str
    attrs: dict = field(default_factory=dict)
    t0: float = 0.0
    t1: float = 0.0
    cpu0: float = 0.0
    cpu1: float = 0.0
    thread: int = 0
    children: list = field(default_factory=list)
    _tracer: Any = None
    _annotation: Any = None

    @property
    def wall_s(self) -> float:
        return self.t1 - self.t0

    @property
    def cpu_s(self) -> float:
        return self.cpu1 - self.cpu0

    def set(self, **attrs):
        """Attach attributes mid-span (e.g. a route decided after entry)."""
        self.attrs.update(attrs)
        return self

    def transfer(self, *, h2d=None, d2h=None):
        """Add the bytes this span moves: ``h2d`` a pytree whose HOST
        leaves go to the device, ``d2h`` one whose DEVICE leaves come to
        the host (leaves on the other side move nothing and count 0)."""
        if h2d is not None:
            self.attrs["h2d_bytes"] = self.attrs.get("h2d_bytes", 0) \
                + tree_bytes(h2d, on_device=False)
        if d2h is not None:
            self.attrs["d2h_bytes"] = self.attrs.get("d2h_bytes", 0) \
                + tree_bytes(d2h, on_device=True)
        return self

    def __enter__(self):
        if self._tracer.profiler:
            self._annotation = _trace_annotation(PROFILER_PREFIX + self.name)
            self._annotation.__enter__()
        self.t0 = time.perf_counter()
        self.cpu0 = time.process_time()
        self.thread = threading.get_ident()
        self._tracer._push(self)
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        self.cpu1 = time.process_time()
        self._tracer._pop(self)
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
            self._annotation = None
        return False


def _trace_annotation(name: str):
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


def tree_bytes(tree, *, on_device: bool) -> int:
    """Bytes of a pytree's array leaves that live on the device
    (``on_device``) or on the host: a ``jax.Array`` is on the device,
    anything else with ``nbytes`` (NumPy) on the host."""
    from jax.tree_util import tree_leaves
    return sum(int(leaf.nbytes) for leaf in tree_leaves(tree)
               if hasattr(leaf, "nbytes")
               and hasattr(leaf, "devices") == on_device)


class _NullSpan:
    """Shared do-nothing span: the only object the default tracer hands
    out, so uninstrumented runs allocate nothing per span site."""
    __slots__ = ()
    name = ""
    attrs: dict = {}
    children: list = []
    wall_s = 0.0
    cpu_s = 0.0

    def set(self, **attrs):
        return self

    def transfer(self, *, h2d=None, d2h=None):
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class NullTracer:
    """Default tracer: every span is the shared no-op. ``enabled`` lets
    hot paths skip even attr-dict construction when they care."""
    enabled = False

    def span(self, name, **attrs):
        return _NULL_SPAN

    def roots(self):
        return []

    def clear(self):
        pass


class Tracer:
    """Collecting tracer. Thread-safe: each thread keeps its own open-span
    stack (nesting = call structure per thread); finished top-level spans
    append to a lock-protected list. ``max_spans`` bounds memory — spans
    past it are counted in ``n_dropped`` instead of stored.
    ``profiler``: mirror every span into the JAX profiler as a
    ``TraceAnnotation`` named ``florida.<span name>``."""
    enabled = True

    def __init__(self, max_spans: int = 200_000, profiler: bool = False):
        self.max_spans = max_spans
        self.profiler = profiler
        self.n_dropped = 0
        self.n_spans = 0
        self.epoch = time.perf_counter()     # perfetto ts origin
        self.epoch_unix = time.time()
        self._roots: list = []
        self._lock = threading.Lock()
        self._tls = threading.local()

    # pickle safety: the service layer is pickled by the CLI session file;
    # locks and thread-locals are not picklable and hold no data we keep
    def __getstate__(self):
        d = dict(self.__dict__)
        d.pop("_lock", None)
        d.pop("_tls", None)
        return d

    def __setstate__(self, d):
        self.__dict__.update(d)
        self._lock = threading.Lock()
        self._tls = threading.local()

    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def span(self, name, **attrs) -> Span:
        return Span(name=name, attrs=attrs, _tracer=self)

    def _push(self, sp: Span):
        self._stack().append(sp)

    def _pop(self, sp: Span):
        st = self._stack()
        if st and st[-1] is sp:
            st.pop()
        self.n_spans += 1
        if self.n_spans > self.max_spans:
            self.n_dropped += 1
            return
        if st:
            st[-1].children.append(sp)
        else:
            with self._lock:
                self._roots.append(sp)

    def current(self) -> Span | None:
        st = self._stack()
        return st[-1] if st else None

    def roots(self) -> list:
        with self._lock:
            return list(self._roots)

    def clear(self):
        with self._lock:
            self._roots = []
        self.n_spans = 0
        self.n_dropped = 0

    def find_roots(self, name=None, **attrs) -> list:
        """Finished top-level spans matching a name and/or attr values."""
        out = []
        for sp in self.roots():
            if name is not None and sp.name != name:
                continue
            if any(sp.attrs.get(k) != v for k, v in attrs.items()):
                continue
            out.append(sp)
        return out

    # -- Perfetto export ---------------------------------------------------

    def to_perfetto(self) -> dict:
        """Chrome ``trace_events`` JSON (complete 'X' events, µs): load in
        ui.perfetto.dev / chrome://tracing. One track per thread."""
        events = [{"name": "process_name", "ph": "M", "pid": 0,
                   "args": {"name": "florida"}}]
        tid_of: dict = {}
        for root in self.roots():
            self._emit(root, events, tid_of)
        for ident, tid in sorted(tid_of.items(), key=lambda kv: kv[1]):
            events.append({"name": "thread_name", "ph": "M", "pid": 0,
                           "tid": tid, "args": {"name": f"thread-{tid}"}})
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"epoch_unix": self.epoch_unix,
                              "n_spans": self.n_spans,
                              "n_dropped": self.n_dropped}}

    def _emit(self, sp: Span, events: list, tid_of: dict):
        tid = tid_of.setdefault(sp.thread, len(tid_of))
        args = {k: _jsonable(v) for k, v in sp.attrs.items()}
        args["cpu_ms"] = round(sp.cpu_s * 1e3, 3)
        events.append({
            "name": sp.name, "ph": "X", "pid": 0, "tid": tid,
            "ts": round((sp.t0 - self.epoch) * 1e6, 3),
            "dur": round(max(sp.wall_s, 0.0) * 1e6, 3),
            "args": args,
        })
        for ch in sp.children:
            self._emit(ch, events, tid_of)

    def export_perfetto(self, path: str) -> str:
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_perfetto(), f)
        return path


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    return str(v)


def stage_list(span: Span, base: float | None = None, depth: int = 0
               ) -> list:
    """Flatten a span subtree into flight-recorder stage rows:
    ``{name, t0_ms (offset from the subtree root), dur_ms, depth}`` —
    enough to rebuild a timeline without the live tracer."""
    base = span.t0 if base is None else base
    out = [{"name": span.name, "t0_ms": round((span.t0 - base) * 1e3, 3),
            "dur_ms": round(span.wall_s * 1e3, 3), "depth": depth}]
    for ch in span.children:
        out.extend(stage_list(ch, base, depth + 1))
    return out


# ----------------------------------------------------------------------
# module-global tracer (the `logging` pattern: one process-wide sink)
# ----------------------------------------------------------------------

_TRACER: Any = NullTracer()


def get_tracer():
    return _TRACER


def set_tracer(tracer) -> Any:
    """Install the process tracer; returns the previous one."""
    global _TRACER
    prev, _TRACER = _TRACER, tracer
    return prev


def enabled() -> bool:
    return _TRACER.enabled


def span(name, **attrs):
    """``with tracing.span("mask_apply", task_id=3) as sp:`` — the one
    call every instrumented site makes; a no-op under the default
    :class:`NullTracer`."""
    return _TRACER.span(name, **attrs)


@contextmanager
def use_tracer(tracer):
    """Scoped ``set_tracer`` (tests, benches): restores the previous
    tracer on exit."""
    prev = set_tracer(tracer)
    try:
        yield tracer
    finally:
        set_tracer(prev)


# ----------------------------------------------------------------------
# jit cache probe
# ----------------------------------------------------------------------

# the repo's SHARED jitted entry points (ROADMAP fixed-shape contracts
# live here: async batch pad classes -> _flat_local_dp_rows_jit /
# _buffer_write_masked, streaming waves -> _wave_limb_state). Looked up
# lazily through sys.modules so importing tracing never imports jax.
_JIT_ENTRY_POINTS = (
    ("repro.core.dp", "_flat_local_dp_jit"),
    ("repro.core.dp", "_flat_local_dp_rows_jit"),
    ("repro.core.dp", "_flat_clip_jit"),
    ("repro.core.privacy_engine", "_cohort_interims"),
    ("repro.core.privacy_engine", "_cohort_interims_churn"),
    ("repro.core.privacy_engine", "_wave_limb_state"),
    ("repro.core.privacy_engine", "ravel_rows"),
    ("repro.core.secure_agg", "_shard_limbs_jit"),
    ("repro.core.secure_agg", "_merge_jit"),
    ("repro.core.secure_agg", "_finalize_jit"),
    ("repro.core.strategies", "_buffer_write"),
    ("repro.core.strategies", "_buffer_write_masked"),
    ("repro.core.strategies", "_drain_apply"),
    ("repro.core.dropout", "_bucket_corrections"),
)

# (label, id(fn)) -> weak reference to fn: per-instance executables
# (CohortEngine's vmapped cohort fns) registered at creation time. Weak, so
# that the probe neither keeps an engine's programs alive nor, through
# their closures, the frozen base its spec holds; an entry leaves with the
# function it names.
_DYNAMIC_JITS: dict = {}


def register_jit(label: str, fn):
    """Track a dynamically created jitted callable in the cache probe
    (no-op for objects without ``_cache_size``) for as long as it lives."""
    if hasattr(fn, "_cache_size"):
        k = (label, id(fn))
        _DYNAMIC_JITS[k] = weakref.ref(
            fn, lambda _, k=k: _DYNAMIC_JITS.pop(k, None))
    return fn


def jit_cache_sizes() -> dict:
    """{entry-point label: compiled-executable count}. Only modules
    ALREADY imported are probed — the probe never triggers imports."""
    out = {}
    for mod_name, attr in _JIT_ENTRY_POINTS:
        mod = sys.modules.get(mod_name)
        fn = getattr(mod, attr, None) if mod is not None else None
        if fn is not None and hasattr(fn, "_cache_size"):
            out[f"{mod_name.rsplit('.', 1)[-1]}.{attr}"] = \
                int(fn._cache_size())
    for (label, _), ref in list(_DYNAMIC_JITS.items()):
        fn = ref()
        if fn is not None:
            out[label] = out.get(label, 0) + int(fn._cache_size())
    return out


def jit_cache_total() -> int:
    """Total compiled executables across the registered entry points —
    per-round deltas of this are the ``jit_cache_misses`` counter."""
    return sum(jit_cache_sizes().values())


# ----------------------------------------------------------------------
# flight recorder
# ----------------------------------------------------------------------


class FlightRecorder:
    """Append-only per-task JSONL round transcripts under ``root``:
    ``<root>/task_<id>.jsonl``, one structured event per line. Holds only
    the directory path (pickles with the CLI session; files open per
    append)."""

    def __init__(self, root: str):
        self.root = root

    def path(self, task_id: int) -> str:
        return os.path.join(self.root, f"task_{int(task_id)}.jsonl")

    def record(self, task_id: int, event: dict) -> dict:
        os.makedirs(self.root, exist_ok=True)
        event = dict(event, ts_unix=round(time.time(), 3))
        with open(self.path(task_id), "a") as f:
            f.write(json.dumps(event, default=_jsonable) + "\n")
        return event

    def read(self, task_id: int) -> list:
        p = self.path(task_id)
        if not os.path.exists(p):
            return []
        with open(p) as f:
            return [json.loads(line) for line in f if line.strip()]

    def task_ids(self) -> list:
        if not os.path.isdir(self.root):
            return []
        out = []
        for fn in os.listdir(self.root):
            if fn.startswith("task_") and fn.endswith(".jsonl"):
                try:
                    out.append(int(fn[len("task_"):-len(".jsonl")]))
                except ValueError:
                    pass
        return sorted(out)


def round_event(*, round_idx: int, cohort, survivors, n_shards: int = 0,
                stage2_route: str | None = None, voided: bool = False,
                void_reason: str | None = None, span_tree: Span | None = None,
                metrics: dict | None = None) -> dict:
    """Build the flight-recorder round transcript event. ``span_tree`` is
    the round's root span — its subtree becomes the ``stages`` rows."""
    ev = {
        "event": "round_voided" if voided else "round",
        "round": int(round_idx),
        "cohort": list(cohort),
        "survivors": list(survivors),
        "n_dropped": len(cohort) - len(survivors),
    }
    if n_shards:
        ev["n_shards"] = int(n_shards)
    if stage2_route:
        ev["stage2_route"] = stage2_route
    if void_reason:
        ev["void_reason"] = void_reason
    if metrics:
        ev["metrics"] = {k: _jsonable(v) for k, v in metrics.items()}
    if span_tree is not None and not isinstance(span_tree, _NullSpan):
        ev["stages"] = stage_list(span_tree)
        ev["wall_ms"] = round(span_tree.wall_s * 1e3, 3)
    return ev


def perfetto_from_flight(events: list, task_id: int) -> dict:
    """Rebuild a Perfetto ``trace_events`` timeline from recorded flight
    events alone (no live tracer needed): rounds lay out back-to-back on
    one track, each round's recorded ``stages`` at their stored offsets."""
    out = [{"name": "process_name", "ph": "M", "pid": 0,
            "args": {"name": f"florida-task-{task_id}"}}]
    cursor_us = 0.0
    for ev in events:
        stages = ev.get("stages")
        if not stages:
            wall = float(ev.get("wall_ms", 1.0)) * 1e3
            out.append({"name": ev.get("event", "round"), "ph": "X",
                        "pid": 0, "tid": 0, "ts": cursor_us, "dur": wall,
                        "args": {"round": ev.get("round")}})
            cursor_us += wall
            continue
        for row in stages:
            args = {"round": ev.get("round"), "depth": row["depth"]}
            out.append({"name": row["name"], "ph": "X", "pid": 0,
                        "tid": row["depth"],
                        "ts": cursor_us + row["t0_ms"] * 1e3,
                        "dur": row["dur_ms"] * 1e3, "args": args})
        cursor_us += float(ev.get("wall_ms",
                                  stages[0]["dur_ms"])) * 1e3
    return {"traceEvents": out, "displayTimeUnit": "ms"}
