"""The reduction from a profiler trace to numbers.

Everything here works on plain lists of events ``(plane, line, name,
start_ns, duration_ns)`` so that it can be tested on a list written by hand;
`read_xplane` turns the profiler's ``.xplane.pb`` into such a list with
nothing but JAX.

On a TPU the device planes are called ``/device:TPU:<n>``; their ``XLA Ops``
line holds one event per executed operation (kernels included) and is what
"busy" is the union of. Host spans of the harness are ``TraceAnnotation``
events whose names start with ``bench/``; they sit on the host's thread lines,
on the same clock.
"""

from __future__ import annotations

from pathlib import Path

Event = tuple  # (plane, line, name, start_ns, duration_ns)

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench/"


def read_xplane(trace_dir: Path) -> list[Event]:
    """All events of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    events = []
    for plane in data.planes:
        keep_all = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            for ev in line.events:
                if keep_all or ev.name.startswith(SPAN_PREFIX):
                    events.append((plane.name, line.name, ev.name, int(ev.start_ns), int(ev.duration_ns)))
    return events


def device_planes(events: list[Event]) -> list[str]:
    return sorted({e[0] for e in events if e[0].startswith(DEVICE_PLANE_PREFIX)})


# Control-flow wrappers: one event spans the whole loop, its body's
# operations are events of their own. Left in, a scanned train step would
# read as one operation that is busy from end to end.
WRAPPERS = ("while", "conditional", "call")


def short_name(name: str) -> str:
    """``%fusion.4283 = bf16[...] fusion(...)`` -> ``fusion``: the
    instruction's name without its number, which is what stays the same from
    one compile to the next. A Mosaic kernel's instruction is named after the
    kernel (``_gather_2d.13`` -> ``_gather_2d``)."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    base, dot, num = head.rpartition(".")
    return base if dot and num.isdigit() else head


def op_events(events: list[Event], plane: str) -> list[Event]:
    """The plane's executed operations, control-flow wrappers left out, each
    renamed to its `short_name`."""
    out = []
    for e in events:
        if e[0] == plane and e[1] == OPS_LINE:
            name = short_name(e[2])
            if name not in WRAPPERS:
                out.append((e[0], e[1], name, e[3], e[4]))
    return out


def host_spans(events: list[Event]) -> list[tuple[str, int, int]]:
    """The harness's spans in the trace as ``(name, start_ns, end_ns)``."""
    return sorted(
        ((e[2][len(SPAN_PREFIX):], e[3], e[3] + e[4]) for e in events if e[2].startswith(SPAN_PREFIX)),
        key=lambda s: s[1],
    )


def merged(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """The union of ``(start, end)`` intervals as disjoint sorted intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ops: list[Event]) -> int:
    """Nanoseconds in which at least one operation ran."""
    return sum(e - s for s, e in merged([(o[3], o[3] + o[4]) for o in ops]))


def kernel_ns(ops: list[Event], needles: tuple[str, ...]) -> tuple[int, int]:
    """Summed duration and count of the operations whose name holds any of
    ``needles``."""
    hit = [o[4] for o in ops if any(n in o[2] for n in needles)]
    return sum(hit), len(hit)


def top_ops(ops: list[Event], n: int = 10) -> list[list]:
    """``[name, seconds]`` of the operations that took most time, summed by name."""
    total: dict[str, int] = {}
    for o in ops:
        total[o[2]] = total.get(o[2], 0) + o[4]
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def idle_gaps(ops: list[Event], spans: list[tuple[str, int, int]], n: int = 10) -> list[list]:
    """``[what the host was doing, seconds]`` of the longest gaps between
    operations: each gap goes to the harness span that covers most of it, or
    to ``"(no harness span)"``. Gaps of one span name are summed."""
    busy = merged([(o[3], o[3] + o[4]) for o in ops])
    by_span: dict[str, int] = {}
    for (_, end), (start, _) in zip(busy, busy[1:]):
        best, cover = "(no harness span)", 0
        for name, s, e in spans:
            c = min(e, start) - max(s, end)
            if c > cover:
                best, cover = name, c
        by_span[best] = by_span.get(best, 0) + (start - end)
    ranked = sorted(by_span.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def summarize(events: list[Event], chips: int) -> dict:
    """``busy_s`` averaged over the chips used, and the breakdown of chip 0."""
    planes = device_planes(events)[:chips]
    if not planes:
        raise ValueError("the trace holds no device plane")
    per_chip = [op_events(events, p) for p in planes]
    return {
        "busy_s": sum(busy_ns(ops) for ops in per_chip) / len(per_chip) / 1e9,
        "ops": per_chip[0],
        "breakdown": {
            "device_ops": top_ops(per_chip[0]),
            "idle_gaps": idle_gaps(per_chip[0], host_spans(events)),
        },
    }


def describe(events: list[Event], top: int = 40) -> dict:
    """What a trace holds, for a look by hand: per plane and line the number
    of events and the names that took most time."""
    out: dict = {}
    for plane, line, name, _start, dur in events:
        name = short_name(name) if plane.startswith(DEVICE_PLANE_PREFIX) else name
        names = out.setdefault(plane, {}).setdefault(line, {})
        n, ns = names.get(name, (0, 0))
        names[name] = (n + 1, ns + dur)
    return {
        plane: {
            line: {
                "events": sum(n for n, _ in names.values()),
                "top": sorted(([k, n, ns / 1e9] for k, (n, ns) in names.items()), key=lambda r: -r[2])[:top],
            }
            for line, names in lines.items()
        }
        for plane, lines in out.items()
    }


def sample_around_second_module(events: list[Event], half_width_ns: int = 1_500_000) -> list[list]:
    """A small recorded piece of a trace for the tests: every kept event that
    starts within ``half_width_ns`` of the start of the second executed module
    (the seam between two dispatches, where a gap can be), names cut to 120
    characters."""
    modules = sorted(e[3] for e in events if e[1] == "XLA Modules")
    if len(modules) < 2:
        return []
    at = modules[1]
    return [
        [plane, line, name[:120], start, dur]
        for plane, line, name, start, dur in events
        if abs(start - at) <= half_width_ns or (start <= at <= start + dur and line != OPS_LINE)
    ]
