"""Device time by the program's scope and phase.

The program (``eventstreamgpt_tpu/utils/scopes.py``) writes ``es.<scope>``
into the ``op_name`` of every operation traced under a scope; JAX writes
``transpose(...)`` around the backward pass and ``rematted_computation``
around what a remat policy computes again. This module reads those paths back
from a device trace: the innermost ``es.`` component is the operation's
scope, and the phase is ``recompute`` where the path holds
``rematted_computation``, else ``backward`` where it holds ``transpose(``,
else ``forward``.

Where the ``op_name`` comes from on a TPU (my chip run 1, PR 26): not from an
``XLA Ops`` event's own statistics (they hold the device's offsets only). The
trace's ``/host:metadata`` plane carries, per executed module, the compiled
``HloProto`` (9.9 MB for the chunked CI step); each instruction's
``metadata.op_name`` is read from it by the instruction's name
(``%fusion.4283`` in the event's name). A fusion carries one ``op_name``, its
hero's, whatever it fused: AdamW's update rides in the weight-gradient
fusions and its time goes to their scopes, so `table` also says per scope
how much time the fusions of other scopes that hold its instructions take
(``rides_ns``, an upper bound). The device plane's own table of operations
has a ``tf_op`` statistic too (the same path, then a colon and the type): on
the 1,006 operations both name, scope and phase agree on all, and it names
no scope the module does not, so it is not read.

A named scope is metadata and JAX leaves metadata out of the compile cache's
key, so an executable compiled without the scopes could be served with the old
names. Checked (PR 26): on the CPU it is (a program that differs in a scope
alone hits the cache and its compiled text lacks the name). On the chip it is
not, for the cells' step: in one cache directory, after the parent's tree had
filled it, the change's tree in the same place missed for the chunked step
alone (54 hits, 1 miss) and its trace carried every name. That step holds
Mosaic kernels, whose payload the key does cover. A program without one can
read stale; `by_scope` then says on standard error that the trace holds
operations and no ``es.`` scope, and the metrics read nothing, not 0.

The run's trace directory is not in the record, so it is looked up where
``benchmark/run.py`` lays it out: ``<checkout>/.bench_work/*.<pid>/trace`` of
this process (the readers run before ``run.py`` removes it).
"""

from __future__ import annotations

import os
import re
import sys
from pathlib import Path

from benchmark.harness import trace

PHASES = ("forward", "backward", "recompute")
_SCOPE = re.compile(r"(?:^|/)es\.([A-Za-z0-9_]+)")
_KEY = "_by_scope"

# (instruction, scope | None, phase, start_ns, duration_ns, other scopes inside)
ScopedOp = tuple


def scope_of(op_name: str) -> tuple[str | None, str]:
    """``(scope, phase)`` of one ``op_name`` path."""
    found = _SCOPE.findall(op_name)
    if "rematted_computation" in op_name:
        phase = "recompute"
    elif "transpose(" in op_name:
        phase = "backward"
    else:
        phase = "forward"
    return (found[-1] if found else None), phase


def instruction_name(event_name: str) -> str:
    """``%fusion.4283 = bf16[...] fusion(...)`` -> ``fusion.4283``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


# ------------------------------------------------------------ protobuf, by hand
# The profiler's Python reader shows planes, lines and events, not the tables
# a plane interns its names and per-operation statistics in, nor the compiled
# module the metadata plane carries. A length-delimited walk over the few
# fields needed reads them with nothing installed.
def _varint(buf, i: int) -> tuple[int, int]:
    """The varint at ``buf[i]`` and the index after it."""
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, wire type, value)`` of one message: an int for a
    varint or a fixed-width field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire in (1, 5):
            width = 8 if wire == 1 else 4
            value = int.from_bytes(buf[i:i + width], "little")
            i += width
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield number, wire, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_value(view):
    """The value of one entry of a protobuf map."""
    return next((v for number, _wire, v in _fields(view) if number == 2), None)


def interned_events(plane) -> dict:
    """``{name: {statistic's name: value}}`` of the events one ``XPlane``
    interns (a value is a string, a number or bytes)."""
    events, stat_names = {}, {}
    for number, _wire, v in _fields(plane):
        if number == 5:  # map<int64, XStatMetadata>
            ident, label = 0, ""
            for n2, _w2, v2 in _fields(_map_value(v)):
                if n2 == 1:
                    ident = v2
                elif n2 == 2:
                    label = _text(v2)
            stat_names[ident] = label
        elif number == 4:  # map<int64, XEventMetadata>
            name, stats = "", []
            for n2, _w2, v2 in _fields(_map_value(v)):
                if n2 == 2:
                    name = _text(v2)
                elif n2 == 5:
                    stats.append(v2)
            events[name] = stats
    out = {}
    for name, stats in events.items():
        out[name] = {}
        for raw in stats:
            ident, value = 0, None
            for n3, _w3, v3 in _fields(raw):
                if n3 == 1:
                    ident = v3
                elif n3 in (3, 4):
                    value = v3
                elif n3 == 5:
                    value = _text(v3)
                elif n3 == 6:
                    value = bytes(v3)
            out[name][stat_names.get(ident, str(ident))] = value
    return out


def xspace_planes(path: Path):
    """The raw planes of an ``.xplane.pb`` as ``(name, memoryview)``."""
    data = memoryview(Path(path).read_bytes())
    for number, _wire, v in _fields(data):
        if number == 1:
            name = next((_text(v2) for n2, _w, v2 in _fields(v) if n2 == 2), "")
            yield name, v


def hlo_op_names(hlo_proto) -> tuple[dict, dict]:
    """``({instruction name: op_name}, {instruction name: the scopes of the
    instructions inside the computation it calls})`` of one serialized
    ``HloProto``. An instruction without an ``op_name`` of its own (a fusion
    the compiler gave none) takes the root's of the computation it calls. The
    second dictionary is what a fusion hides: the compiler fuses across
    scopes, the trace has one time for the whole fusion, and that time goes to
    the scope of the fusion's own ``op_name``."""
    module = next((v for n, _w, v in _fields(hlo_proto) if n == 1), None)
    if module is None:
        return {}, {}
    own, calls, roots, by_id, members = {}, {}, {}, {}, {}
    for number, _wire, comp in _fields(module):
        if number != 3:
            continue
        comp_id, root_id, held = None, None, []
        for n2, _w2, v2 in _fields(comp):
            if n2 == 5:
                comp_id = v2
            elif n2 == 6:
                root_id = v2
            elif n2 == 2:
                name, op_name, ident, called = "", "", None, None
                for n3, w3, v3 in _fields(v2):
                    if n3 == 1:
                        name = _text(v3)
                    elif n3 == 35:
                        ident = v3
                    elif n3 == 7:
                        op_name = next((_text(v4) for n4, _w4, v4 in _fields(v3) if n4 == 2), "")
                    elif n3 == 38 and called is None:
                        # packed or not: the first called computation's id
                        called = v3 if w3 == 0 else _varint(v3, 0)[0]
                own[name] = op_name
                by_id[ident] = name
                held.append(name)
                if called is not None:
                    calls[name] = called
        roots[comp_id] = root_id
        members[comp_id] = held

    def resolve(name, depth=0):
        if own.get(name) or name not in calls or depth > 8:
            return own.get(name, "")
        return resolve(by_id.get(roots.get(calls[name])), depth + 1)

    inside = {}
    for name, comp_id in calls.items():
        found = {scope_of(own[m])[0] for m in members.get(comp_id, ())} - {None}
        if found:
            inside[name] = found
    return {name: resolve(name) for name in own}, inside


# ------------------------------------------------------------------ the reader
def _newest_xplane(trace_dir: Path) -> Path | None:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    return files[-1] if files else None


def op_name_sources(path: Path) -> tuple[dict, dict]:
    """``({instruction name: op_name}, {instruction name: scopes inside})``
    from the compiled modules the trace's metadata plane carries; empty where
    it carries none. Where two modules share an instruction's name, the larger
    module's entry stands (the window runs the step program and nothing else
    of size)."""
    modules = [
        hlo_op_names(memoryview(stats["Hlo Proto"]))
        for name, plane in xspace_planes(path) if name == "/host:metadata"
        for stats in interned_events(plane).values() if isinstance(stats.get("Hlo Proto"), bytes)
    ]
    by_instruction: dict = {}
    inside: dict = {}
    for names, held in sorted(modules, key=lambda m: len(m[0])):
        by_instruction.update({k: v for k, v in names.items() if v})
        inside.update(held)
    return by_instruction, inside


def scoped(raw: list[tuple], by_instruction: dict, inside: dict) -> list[ScopedOp]:
    """``raw`` is the ``XLA Ops`` line as ``(event name, start_ns,
    duration_ns)``; control-flow wrappers are left out as `trace.op_events`
    leaves them out."""
    out = []
    for name, start, dur in raw:
        if trace.short_name(name) in trace.WRAPPERS:
            continue
        instruction = instruction_name(name)
        scope, phase = scope_of(by_instruction.get(instruction, ""))
        others = tuple(sorted(inside.get(instruction, set()) - {scope}))
        out.append((instruction, scope, phase, start, dur, others))
    return out


def read_scoped_ops(trace_dir: Path) -> list[ScopedOp]:
    """Chip 0's executed operations, each under its scope and phase."""
    from jax.profiler import ProfileData

    path = _newest_xplane(trace_dir)
    if path is None:
        return []
    data = ProfileData.from_file(str(path))
    planes = sorted(p.name for p in data.planes if p.name.startswith(trace.DEVICE_PLANE_PREFIX))
    if not planes:
        return []
    raw = [
        (ev.name, int(ev.start_ns), int(ev.duration_ns))
        for plane in data.planes if plane.name == planes[0]
        for line in plane.lines if line.name == trace.OPS_LINE
        for ev in line.events
    ]
    return scoped(raw, *op_name_sources(path))


def find_trace_dir() -> Path | None:
    """This process's trace directory as ``benchmark/run.py`` lays it out."""
    work = Path(__file__).resolve().parents[2] / ".bench_work"
    found = [p / "trace" for p in work.glob(f"*.{os.getpid()}") if (p / "trace").is_dir()]
    return found[0] if found else None


def table(ops: list[ScopedOp], top: int = 12) -> dict:
    """Nanoseconds by ``(scope, phase)``; ``busy_ns`` is the union of all the
    operations' intervals, so operations that overlap are not counted twice
    there; ``phase_ns`` sums every operation, scoped or not, by its phase;
    ``rides_ns`` is, per scope, the time of the operations that go to another
    scope (or to none) and hold instructions of this one inside: an upper
    bound on what a fusion hides; ``unscoped_top`` names what carries no
    scope."""
    by: dict = {}
    unscoped: dict = {}
    rides: dict = {}
    phases = dict.fromkeys(PHASES, 0)
    for instruction, scope, phase, _start, dur, others in ops:
        phases[phase] += dur
        for other in others:
            rides[other] = rides.get(other, 0) + dur
        if scope is None:
            short = trace.short_name(instruction)
            unscoped[short] = unscoped.get(short, 0) + dur
        else:
            by[(scope, phase)] = by.get((scope, phase), 0) + dur
    return {
        "scoped_ns": sum(by.values()),
        "busy_ns": sum(e - s for s, e in trace.merged([(o[3], o[3] + o[4]) for o in ops])),
        "table": by,
        "phase_ns": phases,
        "rides_ns": rides,
        "unscoped_top": [[k, v] for k, v in sorted(unscoped.items(), key=lambda kv: -kv[1])[:top]],
    }


def render(result: dict, steps: int) -> str:
    """The table as text: device ms per optimizer step by scope and phase."""
    per = 1e6 * max(steps, 1)
    cells, rides = result["table"], result["rides_ns"]
    scopes = sorted({s for s, _ in cells} | set(rides), key=lambda s: -sum(cells.get((s, p), 0) for p in PHASES))
    head = f"{'scope (ms a step)':<22}" + "".join(f"{p:>11}" for p in PHASES)
    lines = [head + f"{'all':>11}{'rides in others':>17}"]
    for s in scopes:
        row = [cells.get((s, p), 0) / per for p in PHASES]
        lines.append(
            f"es.{s:<19}" + "".join(f"{v:11.3f}" for v in row) + f"{sum(row):11.3f}{rides.get(s, 0) / per:17.3f}"
        )
    busy = result["busy_ns"]
    lines.append(
        f"scoped {result['scoped_ns'] / per:.3f} of {busy / per:.3f} busy"
        f" ({100.0 * result['scoped_ns'] / max(busy, 1):.2f}%); without a scope:"
    )
    lines += [f"  {name:<40}{ns / per:11.3f}" for name, ns in result["unscoped_top"]]
    return "\n".join(lines)


def by_scope(record: dict) -> dict | None:
    """The reduction of this run's trace, computed once per record and printed
    once on standard error; ``None`` where the trace holds no ``es.`` scope."""
    if _KEY not in record:
        trace_dir = find_trace_dir()
        ops = read_scoped_ops(trace_dir) if trace_dir else []
        result = table(ops)
        if result["table"]:
            print(render(result, record["counters"].get("steps", 1)), file=sys.stderr, flush=True)
        elif ops:
            print(
                f"scopes: the trace holds {len(ops)} device operations and no es. scope"
                " (a parent without scopes, or a stale executable from the compile cache?)",
                file=sys.stderr, flush=True,
            )
        record[_KEY] = result if result["table"] else None
    return record[_KEY]


def _train_table(record: dict) -> dict | None:
    """`by_scope` of a training cell's record; the metrics below move
    ``train_events_per_s`` and read nothing in a cell that does not report it."""
    if "train_events_per_s" not in record.get("end_to_end", {}):
        return None
    return by_scope(record)


def device_ms(record: dict, scopes: tuple[str, ...]) -> float | None:
    """Device ms per optimizer step under ``scopes``, all phases summed."""
    result = _train_table(record)
    steps = record["counters"].get("steps")
    if result is None or not steps:
        return None
    ns = sum(v for (s, _p), v in result["table"].items() if s in scopes)
    return ns / 1e6 / steps


def share_pct(record: dict, phase: str | None = None) -> float | None:
    """Share of ``busy_ns`` under any scope, or in one phase (scoped or not:
    the phase is read from the path, which every operation has)."""
    result = _train_table(record)
    if result is None or not result["busy_ns"]:
        return None
    if phase is None:
        return 100.0 * result["scoped_ns"] / result["busy_ns"]
    return 100.0 * result["phase_ns"].get(phase, 0) / result["busy_ns"]
