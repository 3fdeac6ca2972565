"""Device time by the program's own names: where does my step go.

One reader of the ``.xplane.pb`` the JAX profiler writes, for the operator
(:func:`profile_step`) and for whoever holds a trace already (:func:`load` +
:func:`by_name`). It reads the file itself (a protobuf wire decoder of sixty
lines, no dependency), never the viewer's ``trace.json.gz`` export, whose event
count is capped. Nothing here runs, and nothing imports this module, until a
profile is asked for.

What a TPU trace holds (libtpu 0.0.34, looked at before this was written): a
plane ``/device:TPU:<n>`` per chip; its line ``XLA Ops`` has one event per
executed HLO instruction, whose METADATA (shared by every execution of the
instruction) is named by the instruction's text and carries the stat ``tf_op``:
the instruction's ``op_name`` with a colon after it, named scopes and kernel
names included (``jit(step)/hvd_ssd_scan/while/body/closed_call/dot_general:``).
A ``while`` has no ``tf_op`` of its own and spans the ops of its body on the
same line; ``Async XLA Ops`` spans an asynchronous pair from ``-start`` to
``-done``. Host threads are lines of ``/host:CPU``;
``jax.profiler.TraceAnnotation``\\ s are events there, on the same clock.

The table (:func:`by_name`) is a PARTITION of the device's busy time: every
moment belongs to the innermost event covering it, and to the last component
of that event's ``op_name`` that is one of ``common/device_names.py``'s names
(else to the nearest named event around it, else to ``unnamed``). A loop and
its body count once, and a kernel inside a named loop counts as the kernel.
"""

from __future__ import annotations

import glob
import os
import re
import tempfile
from typing import Callable, NamedTuple, Optional

from ..common import device_names

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE_OPCODES = ("all-reduce", "reduce-scatter", "all-gather",
                      "all-to-all", "collective-permute")
UNNAMED = "unnamed"
UNNAMED_OPS = 5     # rows of the table that say what ``unnamed`` holds
# The operator entry's own host annotations (the benchmark passes its own).
STEP_ANNOTATION = "hvd_profile_step"
SYNC_ANNOTATION = "hvd_profile_sync"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$", re.S)
# The opcode is the first lower-case word directly followed by "(" after the
# shape: shapes hold only upper-case tags before a parenthesis (T(8,128)).
_OPCODE = re.compile(r"(?:^|[\s)}\]])([a-z][a-z\-]*[a-z])\(")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


class Op(NamedTuple):
    start_ns: float
    end_ns: float
    instruction: str
    opcode: Optional[str]
    op_name: str


# ------------------------------------------------------------- wire format
# XSpace{planes=1}; XPlane{name=2, lines=3, event_metadata=4, stat_metadata=5}
# XLine{name=2, timestamp_ns=3, events=4}; XEvent{metadata_id=1, offset_ps=2,
# duration_ps=3}; XEventMetadata{id=1, name=2, stats=5}; XStatMetadata{id=1,
# name=2}; XStat{metadata_id=1, str_value=5, ref_value=7} (tsl xplane.proto).

def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint, the
    bytes (a view, not a copy) for everything else."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value = buf[i:i + size]
            i += size
        else:
            raise ValueError(f"wire type {wire} is not in an xplane")
        yield key >> 3, value


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _map_value(entry):
    """The value (field 2) of one entry of a protobuf map."""
    return next((v for n, v in _fields(entry) if n == 2), b"")


def _plane(buf):
    """``(name, lines, {event metadata id: (name, {stat name: value})})`` of
    one XPlane; a line is ``(name, timestamp_ns, [event bytes])``."""
    name, lines, events, stats = "", [], [], []
    for num, value in _fields(buf):
        if num == 2:
            name = _text(value)
        elif num == 3:
            lines.append(value)
        elif num == 4:
            events.append(_map_value(value))
        elif num == 5:
            stats.append(_map_value(value))
    stat_names = {}
    for meta in stats:
        got = dict(_fields(meta))
        stat_names[got.get(1, 0)] = _text(got.get(2, b""))
    metadata = {}
    for meta in events:
        ident, text, found = 0, "", {}
        for num, value in _fields(meta):
            if num == 1:
                ident = value
            elif num == 2:
                text = _text(value)
            elif num == 5:
                stat = dict(_fields(value))
                if 5 in stat:       # strings are all the reader needs
                    found[stat_names.get(stat.get(1))] = _text(stat[5])
                elif 7 in stat:     # a string kept once, as a stat's name
                    found[stat_names.get(stat.get(1))] = stat_names.get(
                        stat[7], "")
        metadata[ident] = (text, found)
    parsed = []
    for line in lines:
        line_name, stamp, line_events = "", 0, []
        for num, value in _fields(line):
            if num == 2:
                line_name = _text(value)
            elif num == 3:
                stamp = value
            elif num == 4:
                line_events.append(value)
        parsed.append((line_name, stamp, line_events))
    return name, parsed, metadata


def _spans(stamp_ns, events):
    """``(metadata id, start_ns, end_ns)`` of a line's events."""
    for event in events:
        ident = offset = duration = 0
        for num, value in _fields(event):
            if num == 1:
                ident = value
            elif num == 2:
                offset = value
            elif num == 3:
                duration = value
        start = stamp_ns + offset / 1e3
        yield ident, start, start + duration / 1e3


# ------------------------------------------------------------------- load

def parse_instruction(text):
    """``(instruction name, opcode)`` of an event's name or a line of HLO
    text; ``(text, None)`` for anything that is not an instruction."""
    m = _INSTR.match(text)
    if not m:
        return text.strip(), None
    op = _OPCODE.search(m.group(2))
    return m.group(1), op.group(1) if op else None


def op_names_from_hlo(hlo_text):
    """``{instruction: op_name}`` from a compiled module's text
    (``compiled.as_text()``): what a caller who holds the compiled step hands
    to :func:`load` for the events whose metadata carries no ``tf_op``."""
    out = {}
    for line in hlo_text.splitlines():
        m = _OP_NAME.search(line) if " = " in line else None
        if m:
            out[parse_instruction(line.split(", metadata=")[0][:400])[0]] = (
                m.group(1))
    return out


def is_collective(opcode):
    base = (opcode or "").removesuffix("-start").removesuffix("-done")
    return base in COLLECTIVE_OPCODES


def find_xplane(logdir):
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def load(xplane_path, op_names=None):
    """``{"devices": {plane: {"ops": [Op], "async": [Op]}}, "host": {name:
    [(start_ns, end_ns)]}}``: per device plane the events of ``XLA Ops`` and
    the collectives of ``Async XLA Ops``, and every host event by its name,
    all on the profiler's clock. ``op_names`` (``{instruction: op_name}``,
    :func:`op_names_from_hlo`) fills in for events whose metadata has no
    ``tf_op`` (a ``while``; a trace of a runtime that writes none)."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    op_names = op_names or {}
    devices, host = {}, {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        name, lines, metadata = _plane(plane)
        if DEVICE_PLANE.match(name):
            known = {}      # metadata id -> (instruction, opcode, op_name)
            found = devices.setdefault(name, {"ops": [], "async": []})
            for line_name, stamp, events in lines:
                if line_name not in (OPS_LINE, ASYNC_LINE):
                    continue
                for ident, start, end in _spans(stamp, events):
                    if ident not in known:
                        text, stats = metadata.get(ident, ("", {}))
                        instruction, opcode = parse_instruction(text)
                        # tf_op is "<op_name>:<op_type>"
                        op_name = stats.get("tf_op", "").rpartition(":")[0]
                        known[ident] = (instruction, opcode, op_name
                                        or op_names.get(instruction, ""))
                    op = Op(start, end, *known[ident])
                    if line_name == OPS_LINE:
                        found["ops"].append(op)
                    elif is_collective(op.opcode):
                        found["async"].append(op)   # the rest is DMA, not ops
        elif name.startswith("/host:"):
            for _, stamp, events in lines:
                for ident, start, end in _spans(stamp, events):
                    host.setdefault(metadata.get(ident, ("", {}))[0],
                                    []).append((start, end))
    for spans in host.values():
        spans.sort()
    return {"devices": devices, "host": host}


# -------------------------------------------------------------- intervals

def union(intervals):
    """Sorted, disjoint list covering the same points as ``intervals``."""
    merged = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def subtract(merged, holes):
    """The parts of disjoint sorted ``merged`` outside disjoint sorted
    ``holes``."""
    out = []
    for s, e in merged:
        cur = s
        for hs, he in holes:
            if he <= cur:
                continue
            if hs >= e:
                break
            if hs > cur:
                out.append((cur, hs))
            cur = max(cur, he)
        if cur < e:
            out.append((cur, e))
    return out


def covered(span, merged):
    """Length of ``span`` inside the disjoint sorted ``merged``."""
    return (span[1] - span[0]) - sum(e - s for s, e in
                                     subtract([span], merged))


# -------------------------------------------------------------- partition

def name_of(op_name, names=device_names.ALL, prefixes=device_names.PREFIXES):
    """The LAST path component of ``op_name`` that is a program name: equal
    to one of ``names``, or one of ``prefixes`` followed by digits (the bucket
    count of ``hvd_fused_allreduce_k``), bare or inside the transformations
    JAX wraps round a scope (``transpose(jvp(hvd_moe_route))``). Never a
    substring: ``hvd_moe_experts_gmm`` is not ``hvd_moe_experts``. An
    instruction XLA made of several source ops carries all their op_names
    with ``;`` between them: the last name of the whole list. ``None`` without
    one."""
    for part in reversed(op_name.replace(";", "/").split("/")):
        # a transformation wraps the first scope under it: jvp(hvd_moe_route)
        part = part.rpartition("(")[2].rstrip(")")
        if part in names:
            return part
        for prefix in prefixes:
            if part.startswith(prefix) and part[len(prefix):].isdigit():
                return prefix
    return None


def _partition(ops, lo, hi, names, prefixes):
    """``({name: ns}, {name: events}, busy intervals, {(instruction, opcode,
    op_name): ns of UNNAMED})`` of nested events inside ``[lo, hi]``: each
    moment to the innermost event covering it (of two that cover it, the one
    that started later), each event to its own name, else to that of the
    nearest event around it, else to UNNAMED, where the innermost event's
    instruction says what it was."""
    time, count, busy, loose = {}, {}, [], {}
    stack = []      # [(end, name, (instruction, opcode, op_name))]
    clock = lo
    cache = {}

    def close(until):
        """Hand out the time up to ``until`` to the events that cover it."""
        nonlocal clock
        while stack and clock < until:
            end, name, what = stack[-1]
            if end > clock:
                upto = min(end, until)
                time[name] = time.get(name, 0.0) + (upto - clock)
                if name == UNNAMED:
                    loose[what] = loose.get(what, 0.0) + (upto - clock)
                busy.append((clock, upto))
                clock = upto
            if end <= until:
                stack.pop()
        clock = max(clock, until)

    for op in sorted(ops, key=lambda op: (op.start_ns, -op.end_ns)):
        start, end = max(op.start_ns, lo), min(op.end_ns, hi)
        if end <= start:
            continue
        close(start)
        if op.op_name not in cache:
            cache[op.op_name] = name_of(op.op_name, names, prefixes)
        name = cache[op.op_name]
        if name is None:
            name = next((n for e, n, _ in reversed(stack) if e > start),
                        UNNAMED)
        count[name] = count.get(name, 0) + 1
        stack.append((end, name, op[2:]))
    close(hi)
    return time, count, union(busy), loose


def window_of(profile, opens, closes):
    """From the start of the first ``opens`` host annotation to the end of the
    last ``closes`` one, or ``None`` where the trace holds neither."""
    first, last = profile["host"].get(opens), profile["host"].get(closes)
    if not first or not last:
        return None
    return first[0][0], max(e for _, e in last)


def first_device(profile):
    """The lowest-numbered device plane that holds an op, or ``None``."""
    planes = sorted((p for p, d in profile["devices"].items() if d["ops"]),
                    key=lambda p: int(DEVICE_PLANE.match(p).group(1)))
    return planes[0] if planes else None


def by_name(profile, steps, window=None, names=device_names.ALL,
            prefixes=device_names.PREFIXES, host_states=()):
    """A partition of the first device's busy time inside ``window`` (all of
    the trace without one) by the program's names. Returns ``{"seconds":
    {name: s per step}, "calls": {name: events per step}, "unnamed": s per
    step, "busy": s per step, "idle": s per step, "idle_gaps": {host state:
    s per step}, "unnamed_ops": [(instruction, opcode, op_name, s per step)],
    "device": plane, "steps": steps}``; every name of ``names`` is a key of
    ``seconds`` (0.0 where it took no time), and the names and ``unnamed``
    sum to ``busy``. ``unnamed_ops`` are the five instructions that hold most
    of ``unnamed``, the longest first, with the numbers cut out of their names
    (``fusion.N``: every layer's and every leaf's copy of an op is one row):
    what no name of the program reaches. ``host_states`` are host
    annotations: each idle gap goes to the one that covers most of it, else
    to ``other``.
    A profile without a device plane gives the same table, empty."""
    plane = first_device(profile)
    found = profile["devices"].get(plane, {"ops": [], "async": []})
    every = found["ops"] + found["async"]
    if window is None:
        window = ((min(op.start_ns for op in every),
                   max(op.end_ns for op in every)) if every else (0.0, 0.0))
    lo, hi = window
    time, count, busy, loose = _partition(found["ops"], lo, hi, names,
                                          prefixes)
    # An asynchronous collective's span counts where no op covers it (its
    # exposed part), under its own name; its halves on "XLA Ops" are ops.
    for op in found["async"]:
        span = [(max(op.start_ns, lo), min(op.end_ns, hi))]
        exposed = subtract(union(span), busy)
        if exposed:
            name = name_of(op.op_name, names, prefixes) or UNNAMED
            alone = sum(e - s for s, e in exposed)
            time[name] = time.get(name, 0.0) + alone
            if name == UNNAMED:
                loose[op[2:]] = loose.get(op[2:], 0.0) + alone
            busy = union(busy + exposed)
    # numbers cut (fusion.692 -> fusion.N): every layer's and every leaf's
    # copy of an op is one row
    rows = {}
    for (instruction, opcode, op_name), ns in loose.items():
        what = (re.sub(r"\d+", "N", instruction), opcode,
                re.sub(r"\d+", "N", op_name))
        rows[what] = rows.get(what, 0.0) + ns
    busy_ns = sum(e - s for s, e in busy)
    assert abs(sum(time.values()) - busy_ns) <= 1e-6 * max(busy_ns, 1.0), (
        "the names do not sum to the busy time", sum(time.values()), busy_ns)
    gaps = {}
    states = {s: union(profile["host"].get(s, ())) for s in host_states}
    for gap in subtract([(lo, hi)] if hi > lo else [], busy):
        shares = {s: covered(gap, spans) for s, spans in states.items()}
        shares["other"] = gap[1] - gap[0] - sum(shares.values())
        state = max(shares, key=shares.get)
        gaps[state] = gaps.get(state, 0.0) + (gap[1] - gap[0])
    per_step = 1e-9 / max(steps, 1)
    seconds = {name: time.get(name, 0.0) * per_step for name in names}
    return {
        "seconds": seconds,
        "calls": {name: count.get(name, 0) / max(steps, 1)
                  for name in (*names, UNNAMED)},
        "unnamed": time.get(UNNAMED, 0.0) * per_step,
        "unnamed_ops": [(*what, ns * per_step) for what, ns in sorted(
            rows.items(), key=lambda kv: -kv[1])[:UNNAMED_OPS]],
        "busy": busy_ns * per_step,
        "idle": (hi - lo - busy_ns) * per_step,
        "idle_gaps": {s: ns * per_step for s, ns in gaps.items()},
        "device": plane, "steps": steps,
    }


def collective_overlap(profile):
    """Collective intervals of every device by opcode (an asynchronous pair
    once, from ``-start`` to ``-done``) against the non-collective ops of the
    same device: ``[(device, instruction, start_ns, end_ns, hidden_ns)]``."""
    out = []
    for plane, found in sorted(profile["devices"].items()):
        working = union((op.start_ns, op.end_ns) for op in found["ops"]
                        if not is_collective(op.opcode))
        # on "XLA Ops" an asynchronous pair shows as two short halves
        for op in found["async"] + [
                op for op in found["ops"] if is_collective(op.opcode)
                and not op.opcode.endswith(("-start", "-done"))]:
            out.append((plane, op.instruction, op.start_ns, op.end_ns,
                        covered((op.start_ns, op.end_ns), working)))
    return out


# ---------------------------------------------------------------- operator

def format_table(table):
    """The table as text: name, ms/step, share of busy, calls/step; then
    ``unnamed`` with the instructions that hold most of it (instruction,
    ms/step, share of busy, opcode and ``op_name``), idle and the idle gaps
    by host state."""
    busy = table["busy"] or 1.0
    rows = sorted(((s, n) for n, s in table["seconds"].items() if s > 0),
                  reverse=True) + [(table["unnamed"], UNNAMED)]
    lines = [f"{table['device']}: busy {table['busy'] * 1e3:.3f} ms/step over "
             f"{table['steps']} steps, idle {table['idle'] * 1e3:.3f}, named "
             f"{100 * (table['busy'] - table['unnamed']) / busy:.1f}% of busy",
             f"{'name':<28}{'ms/step':>10}{'% busy':>8}{'calls/step':>12}"]
    for seconds, name in rows:
        lines.append(f"{name:<28}{seconds * 1e3:>10.3f}"
                     f"{100 * seconds / busy:>8.2f}"
                     f"{table['calls'].get(name, 0):>12.1f}")
    for instruction, opcode, op_name, seconds in table["unnamed_ops"]:
        # the end of a path says most: its innermost scopes and the primitive
        source = "/".join(op_name.split("/")[-4:]) or "(no op_name)"
        lines.append(f"  {instruction[-26:]:<26}{seconds * 1e3:>10.3f}"
                     f"{100 * seconds / busy:>8.2f}  {opcode or 'op'} "
                     f"{source}")
    for state, seconds in sorted(table["idle_gaps"].items(),
                                 key=lambda kv: -kv[1]):
        lines.append(f"idle, host in {state:<14}{seconds * 1e3:>10.3f}")
    return "\n".join(lines)


def profile_step(run_step: Callable[[], None], steps: int = 5,
                 sync: Optional[Callable[[], None]] = None, compiled=None,
                 logdir: Optional[str] = None) -> dict:
    """Profile ``steps`` calls of a WARMED ``run_step`` and say where the
    device's time went, by the program's names. ``sync`` waits for the last
    step (read its loss); ``compiled`` (the step's ``jax.stages.Compiled``)
    names what the trace leaves without an ``op_name``. Returns
    :func:`by_name`'s table with ``text`` (:func:`format_table`), ``logdir``
    and ``ok``; ``ok`` is False with a ``reason`` where the trace holds no
    device plane (a CPU backend)."""
    import jax

    logdir = logdir or tempfile.mkdtemp(prefix="hvd_profile_")
    with jax.profiler.trace(logdir):
        for _ in range(steps):
            with jax.profiler.TraceAnnotation(STEP_ANNOTATION):
                run_step()
        with jax.profiler.TraceAnnotation(SYNC_ANNOTATION):
            if sync is not None:
                sync()
    profile = load(find_xplane(logdir), op_names_from_hlo(compiled.as_text())
                   if compiled is not None else None)
    # The window is the device's own first op to its last: the trace holds
    # these steps alone, and on a short trace the host's clock may lie a
    # millisecond off the device's.
    table = by_name(profile, steps,
                    host_states=(STEP_ANNOTATION, SYNC_ANNOTATION))
    table.update(ok=table["device"] is not None, logdir=logdir)
    if table["ok"]:
        table["text"] = format_table(table)
    else:
        table["reason"] = ("the trace holds no TPU device plane (a CPU "
                           "backend's trace carries host events only)")
    return table
