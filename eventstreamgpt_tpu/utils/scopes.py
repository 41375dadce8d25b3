"""The names the program writes into a profiler trace.

`scope` puts ``es.<name>`` on the name stack, so that every operation traced
under it carries the name in its ``op_name`` metadata; the innermost ``es.``
component of an ``op_name`` path is the operation's scope, and the phase
(forward, backward, recompute) is read from what JAX writes around it
(``transpose(...)``, ``rematted_computation``). `host_span` is a
``TraceAnnotation`` on the host's thread line, ``es.host/<name>``, and one
entry of the process's own host record (`recorded`): what a start-up phase, a
compile, a dispatch's plans cost on the host's clock, with the integer counts
given where the work is done. Both are always on, nothing to switch, and the
compiled arithmetic is the same with and without them.
``benchmark/harness/scopes.py`` reads the scopes back from a device trace and
``benchmark/metrics/`` the record; a name that is not listed here raises, so
the lists below are the whole contract.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
import threading
import time

import jax

SCOPES = (
    "collate",  # the scan body's device-side collation
    "embed",  # the input layer: data embedding, time encoding, static codes
    "norm",  # every LayerNorm of a block and ln_f
    "attn_proj",  # q, k, v and output projections
    "attn_latent",  # latent attention between the normed input and q, k, v: its four
    # down/up projections, the two latent norms, RoPE, the concatenations
    "attn_global",  # what lies between the projections in a global layer
    "attn_local",  # ... in a local layer
    "dep_graph",  # NA's dependency-graph attention and its plumbing
    "ssm_proj",  # a state-space (Mamba-2) mixer's input and output projections
    "ssm_conv",  # its causal convolution inside the segment, silu, the splits
    "ssm_scan",  # softplus, the decays, the chunked scan, the D skip
    "ssm_gate",  # the gate and the grouped norm
    "conv_proj",  # a gated short convolution's input and output projections
    "conv_gate",  # its two gates and its causal taps inside the segment
    "hc_maps",  # a hyper-connected sublayer's maps: the streams' norm, Phi's product, the sigmoids, Sinkhorn
    "hc_mix",  # its pre-mix of the streams, its post/res mix back into them, the sum before ln_f
    "mlp",  # the feed-forward block (the classic MLP, the dense SwiGLU)
    "moe_router",  # a routed layer's logits, sigmoid, top-k and weights
    "moe_dispatch",  # ordering the (row, expert) pairs by expert, gathering rows, combining back
    "moe_experts",  # the grouped products of the experts held here
    "moe_shared",  # the shared expert
    "heads_tte",  # time-to-event head and its log-likelihood
    "heads_cls",  # classification heads and their losses
    "heads_reg",  # regression heads and their losses
    "loss",  # what the output layer does after the three
    "optimizer",  # tx.update + apply_updates
    "health",  # the divergence sentinel's vector
)

HOST_SPANS = (
    "plan",  # making one dispatch's plans; counts: events, slots, pairs_visited, pairs_dense
    "dispatch",  # the step call
    "checkpoint",
    "log_flush",
    "eval",
    # start-up, each where it is done, all with id "startup"
    "startup/import",  # a package's __init__ from its first line to its last (`utils.misc.ImportClock`)
    "startup/config",  # utils.config_tool.load_config
    "startup/dataset_read",  # JaxDataset.__init__
    "startup/device_tables",  # DeviceDataset.create, until the arrays are on the device
    "startup/build_model",  # build_model
    "startup/build_step",  # build_optimizer, make_train_step, make_chunked_train_step
    "startup/state",  # replicate / train()'s place_state
    "startup/restore",  # resume_training_state
    # JAX's own compile events (`_on_duration`), id the program's name
    "compile/trace",
    "compile/lower",
    "compile/backend",  # count: hit, 1 where the persistent cache served it
    "compile/cache_load",
)


def scope(name: str):
    """``jax.named_scope("es.<name>")``."""
    if name not in SCOPES:
        raise ValueError(f"{name!r} is not one of the program's scopes {SCOPES}")
    return jax.named_scope("es." + name)


def scoped(name: str):
    """Decorator: the whole function runs under `scope` ``name`` (a fresh
    context each call, so it nests and is safe across tracing threads)."""
    scope(name)  # a wrong name raises where the function is defined

    def decorate(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with scope(name):
                return fn(*args, **kwargs)

        return wrapped

    return decorate


@dataclasses.dataclass(slots=True)
class Span:
    """One entry of the host record. ``start`` and ``end`` are
    ``time.perf_counter()`` seconds; ``parent`` is the ``seq`` of the span
    that was open on this thread when this one began (self time is duration
    less children); ``id`` is what the spans of one unit of work share:
    ``"startup"``, a dispatch's index, a compiled program's name."""

    seq: int
    name: str
    start: float
    end: float
    parent: int | None
    id: int | str | None
    counts: dict


# Start-up spans are kept, the newest compile spans too (a process makes some
# hundreds at start-up and none after it), and the hot path's spans live in a
# ring, so a week of `train()` holds nothing more. One lock for the three and
# the totals: compile events and a prefetch thread's spans come from threads of
# their own, and a deque read while another thread appends raises.
_lock = threading.Lock()
_startup: list[Span] = []
_compiles: collections.deque[Span] = collections.deque(maxlen=16384)
_ring: collections.deque[Span] = collections.deque(maxlen=4096)
_seq = itertools.count()
_thread = threading.local()  # .open: the seqs and ids of the spans open on this thread; .hit, .load: see `_on_duration`
_totals = {"backend": 0, "hits": 0, "misses": 0}


def _open() -> list:
    try:
        return _thread.open
    except AttributeError:
        _thread.open = []
        return _thread.open


def _append(span: Span) -> None:
    """Under `_lock`."""
    kind = span.name.partition("/")[0]
    (_startup if kind == "startup" else _compiles if kind == "compile" else _ring).append(span)


class host_span:
    """``with host_span(name, id=..., **counts):`` a
    ``TraceAnnotation("es.host/<name>")`` (free when no trace runs) and, as it
    closes, one `Span` of the record. ``id`` is the parent's where not given.
    The body may add to ``.counts`` (integers known only once the work is
    done) and may `drop` a span that turned out to hold no work."""

    __slots__ = ("name", "id", "counts", "seq", "start", "keep", "_parent", "_annotation")

    def __init__(self, name: str, id: int | str | None = None, **counts: int):
        if name not in HOST_SPANS:
            raise ValueError(f"{name!r} is not one of the program's host spans {HOST_SPANS}")
        self.name, self.id, self.counts, self.keep = name, id, counts, True

    def drop(self) -> None:
        self.keep = False

    def __enter__(self):
        open_spans = _open()
        self._parent = open_spans[-1][0] if open_spans else None
        if self.id is None and open_spans:
            self.id = open_spans[-1][1]
        self.seq = next(_seq)
        open_spans.append((self.seq, self.id))
        self._annotation = jax.profiler.TraceAnnotation("es.host/" + self.name)
        self._annotation.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter()
        self._annotation.__exit__(*exc)
        _open().pop()
        if self.keep:
            with _lock:
                _append(Span(self.seq, self.name, self.start, end, self._parent, self.id, self.counts))
        return False


def host_spanned(name: str, id: int | str | None = None):
    """Decorator: every call of the function is one `host_span` ``name``."""
    host_span(name)  # a wrong name raises where the function is defined

    def decorate(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with host_span(name, id=id):
                return fn(*args, **kwargs)

        return wrapped

    return decorate


def record(name: str, start: float, end: float | None = None, id: int | str | None = None, **counts: int) -> Span:
    """A span written after the fact (an import that began before this module
    could be imported; a compile event, which comes with its duration): it
    takes as children the spans this thread has recorded since ``start`` under
    what was open then, so that what an import pulls in (and what compiles
    while it does) nests as it happened. ``end`` is now where not given."""
    if name not in HOST_SPANS:
        raise ValueError(f"{name!r} is not one of the program's host spans {HOST_SPANS}")
    end = time.perf_counter() if end is None else end
    open_spans = _open()
    parent = open_spans[-1][0] if open_spans else None
    span = Span(next(_seq), name, start, end, parent, id, counts)
    with _lock:
        for kept in (_startup, _compiles):
            for earlier in reversed(kept):  # in order of end
                if earlier.end <= start:
                    break
                if earlier.start >= start and earlier.parent == parent:
                    earlier.parent = span.seq
        _append(span)
    return span


_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile/lower",
    "/jax/core/compile/backend_compile_duration": "compile/backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "compile/cache_load",
}


def _on_duration(event: str, seconds: float, fun_name: str = "", **_kw) -> None:
    """JAX's compile events as spans, ``end`` now and ``start`` now less the
    duration. ``backend_compile_duration`` covers the persistent cache's
    retrieval on a hit (`jax/_src/compiler.py::compile_or_get_cached` runs
    inside it), so the retrieval, which JAX reports first and without a name,
    waits for its backend event and becomes that span's child."""
    name = _COMPILE_EVENTS.get(event)
    if name is None:
        return
    if name == "compile/trace":
        # JAX traces every jitted function a program calls inside the program's
        # own trace (fourteen thousand of them in a train step): the outermost
        # trace, which holds them all, is the one that is a span.
        _thread.tracing = getattr(_thread, "tracing", 1) - 1
        if _thread.tracing > 0:
            return
    now = time.perf_counter()
    if name == "compile/cache_load":
        _thread.load = (now - seconds, now)
        return
    # the trace names the function, lowering and the backend its module, "jit(<function>)"
    program = fun_name[4:-1] if fun_name.startswith("jit(") and fun_name.endswith(")") else fun_name
    if name != "compile/backend":
        record(name, now - seconds, now, id=program)
        return
    load, _thread.load = getattr(_thread, "load", None), None
    hit, _thread.hit = getattr(_thread, "hit", 0), 0
    backend = record(name, now - seconds, now, id=program, hit=hit)
    with _lock:
        _totals["backend"] += 1
        if load is not None:
            _append(Span(next(_seq), "compile/cache_load", load[0], load[1], backend.seq, program, {}))


def _on_trace_begins(event: str, _value: float, **_kw) -> None:
    """JAX writes a scalar as a timed region begins: how deep inside other
    traces this thread's trace is (`_on_duration` counts back down)."""
    if event == "/jax/core/compile/jaxpr_trace_duration":
        _thread.tracing = getattr(_thread, "tracing", 0) + 1


def _on_event(event: str, **_kw) -> None:
    which = {"/jax/compilation_cache/cache_hits": "hits", "/jax/compilation_cache/cache_misses": "misses"}.get(event)
    if which is not None:
        _thread.hit = int(which == "hits")
        with _lock:
            _totals[which] += 1


# The process's one listener on JAX's compile events (jax.monitoring keeps a
# listener for the life of the process, so there is exactly this one).
jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_scalar_listener(_on_trace_begins)
jax.monitoring.register_event_listener(_on_event)


def recorded() -> list[Span]:
    """The host record so far, in order of start."""
    with _lock:
        spans = [*_startup, *_compiles, *_ring]
    return sorted(spans, key=lambda s: s.start)


def since(began: float) -> list[Span]:
    """The start-up and compile spans that began at ``began``
    (``time.perf_counter()``) or later, in order of start."""
    found = []
    with _lock:
        for kept in (_startup, _compiles):
            for span in reversed(kept):  # in order of end
                if span.end < began:
                    break
                if span.start >= began:
                    found.append(span)
    return sorted(found, key=lambda s: s.start)


def compile_totals() -> dict:
    """Backend compiles (cache hits among them) and the persistent cache's
    hits and misses, process-wide, since this module was imported."""
    with _lock:
        return dict(_totals)


def self_seconds(spans: list[Span]) -> dict[int, float]:
    """``seq`` -> the span's duration less its direct children's."""
    own = {s.seq: s.end - s.start for s in spans}
    for s in spans:
        if s.parent in own:
            own[s.parent] -= s.end - s.start
    return own


def summary(spans: list[Span], small: float = 0.1) -> dict:
    """What `train()` writes as its ``startup`` line and
    ``scripts/startup_report.py`` prints: self seconds by start-up phase, and
    by compiled program the seconds of its trace (with what it traces inside
    itself), lowering, backend compile (less the retrieval) and cache
    retrieval, with its backend compiles and how many of them the persistent
    cache served; and the counts a start-up phase's spans carry, summed by
    phase (``startup/dataset_read``: subjects, events, data elements).
    Programs of under ``small`` seconds in all are one row, ``(other)``."""
    own = self_seconds(spans)
    by_seq = {s.seq: s for s in spans}
    phases: dict[str, float] = {}
    counts: dict[str, dict] = {}
    programs: dict[str, dict] = {}
    for s in spans:
        kind, _, what = s.name.partition("/")
        if kind == "startup":
            phases[s.name] = phases.get(s.name, 0.0) + own[s.seq]
            if s.counts:
                summed = counts.setdefault(s.name, {})
                for k, n in s.counts.items():
                    summed[k] = summed.get(k, 0) + n
        elif kind == "compile":
            top = s  # the cache's retrieval counts under its backend event's program
            while (up := by_seq.get(top.parent)) is not None and up.name.startswith("compile/"):
                top = up
            row = programs.setdefault(
                str(top.id), {"trace": 0.0, "lower": 0.0, "backend": 0.0, "cache_load": 0.0, "compiles": 0, "hits": 0}
            )
            row[what] += own[s.seq]
            row["compiles"] += what == "backend"
            row["hits"] += s.counts.get("hit", 0)
    seconds = lambda row: row["trace"] + row["lower"] + row["backend"] + row["cache_load"]  # noqa: E731
    other = {"trace": 0.0, "lower": 0.0, "backend": 0.0, "cache_load": 0.0, "compiles": 0, "hits": 0, "programs": 0}
    for program in [p for p, row in programs.items() if seconds(row) < small]:
        row = programs.pop(program)
        other = {k: other[k] + row.get(k, 1) for k in other}  # "programs" counts the rows folded in
    if other["programs"]:
        programs["(other)"] = other
    return {"phases": phases, "counts": counts, "compile": dict(sorted(programs.items(), key=lambda kv: -seconds(kv[1])))}


def seconds_since_process_start() -> float | None:
    """From the kernel's own record of the process's start (Linux's
    ``/proc``); nothing where the platform does not give it."""
    try:
        import os

        with open("/proc/self/stat") as f:
            started_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            return float(f.read().split()[0]) - started_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None
