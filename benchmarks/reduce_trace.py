"""The one reduction from a profiler trace to numbers.

Reads the ``.xplane.pb`` the JAX profiler writes (``jax.profiler.ProfileData``,
nothing but JAX), never the viewer's ``trace.json.gz`` export, whose event
count is capped. Everything the per-layer readers in ``layer_metrics/`` report
about the device comes from :func:`reduce`; the interval arithmetic underneath
(:func:`union`, :func:`covered`, :func:`subtract`) works on plain
``(start, end)`` lists so the tests can drive it by hand.

What a TPU trace looks like (libtpu 0.0.34, looked at by hand before this was
written): one plane per chip, ``/device:TPU:<n>``, with a line ``XLA Ops``
whose events are the executed HLO instructions, named by their HLO text
without metadata (``%fusion.692 = bf16[...] fusion(...), kind=kOutput, ...``),
and a line ``Async XLA Ops`` whose events span an asynchronous pair from its
``-start`` to its ``-done``. Host threads are lines of ``/host:CPU``; the
benchmark's ``jax.profiler.TraceAnnotation``\\ s are events there, on the same
clock. The named scopes of the step are not in the trace at all: they are in
the compiled module's text (``metadata={op_name="jit(..)/bench_fwd_bwd/.."}``),
which the harness hands in and :func:`scopes_from_hlo` maps by instruction
name.

Definitions, fixed here so that no later PR computes them differently:

* an op's kind comes from its HLO opcode first: ``collective`` for all-reduce,
  reduce-scatter, all-gather, all-to-all and collective-permute (synchronous,
  or the ``-start``..``-done`` span of an asynchronous one), ``kernel`` for a
  Mosaic custom call (``custom_call_target="tpu_custom_call"``), ``compute``
  for every other op on ``XLA Ops``;
* busy is the UNION of op intervals on a device inside the window (two
  overlapping ops are not busy twice); idle share is 1 - busy / window;
* a collective's exposed part is the part of its interval during which no
  compute or kernel op runs on that device;
* the window runs from the start of the first ``bench_dispatch`` annotation to
  the end of the last ``bench_fence`` annotation.
"""

from __future__ import annotations

import glob
import os
import re

COLLECTIVE_OPCODES = ("all-reduce", "reduce-scatter", "all-gather",
                      "all-to-all", "collective-permute")
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
# The names the benchmark's own copy of the step and its loop give: host
# annotations around dispatch and fence, named scopes around value_and_grad
# and around the optimizer's update.
DISPATCH = "bench_dispatch"
FENCE = "bench_fence"
SCOPE_FWD_BWD = "bench_fwd_bwd"
SCOPE_OPTIMIZER = "bench_optimizer"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$", re.S)
# The opcode is the first lower-case word directly followed by "(" after the
# shape: shapes hold only upper-case tags before a parenthesis (T(8,128), S(1)).
_OPCODE = re.compile(r"(?:^|[\s)}\]])([a-z][a-z\-]*[a-z])\(")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


# ---------------------------------------------------------------- intervals

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


def length(merged):
    return sum(e - s for s, e in merged)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def covered(span, merged):
    """Length of ``span`` that lies inside the disjoint sorted ``merged``."""
    s0, e0 = span
    total = 0
    for s, e in merged:
        if e <= s0:
            continue
        if s >= e0:
            break
        total += min(e, e0) - max(s, s0)
    return total


def subtract(merged, holes):
    """The parts of disjoint sorted ``merged`` not covered by disjoint sorted
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


# ------------------------------------------------------------------ parsing

def parse_instruction(text):
    """``(instruction name, opcode)`` of an event name or a line of HLO text;
    ``(text, None)`` for anything that is not an instruction."""
    m = _INSTR.match(text)
    if not m:
        return text.strip(), None
    op = _OPCODE.search(m.group(2))
    return m.group(1), op.group(1) if op else None


def op_kind(text, opcode):
    base = (opcode or "").removesuffix("-start").removesuffix("-done")
    if base in COLLECTIVE_OPCODES:
        return "collective"
    if opcode == "custom-call" and KERNEL_MARK in text:
        return "kernel"
    return "compute"


def scopes_from_hlo(hlo_text, scopes=(SCOPE_FWD_BWD, SCOPE_OPTIMIZER)):
    """{instruction name: (scope or "", op_name)} from a compiled module's
    text. The scope is the first of ``scopes`` found in the instruction's
    ``op_name`` metadata."""
    out = {}
    for line in hlo_text.splitlines():
        if " = " not in line:
            continue
        m = _OP_NAME.search(line)
        if not m:
            continue
        name, _ = parse_instruction(line.split(", metadata=")[0][:400])
        op_name = m.group(1)
        out[name] = (next((s for s in scopes if s in op_name), ""), op_name)
    return out


def find_xplane(logdir):
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def load(path):
    """``{"devices": {plane: [op, ...]}, "host": {annotation: [(s, e), ...]}}``
    with times in nanoseconds on the profiler's clock. An op is a dict with
    ``name``, ``opcode``, ``kind``, ``start``, ``end``; an asynchronous pair
    appears once, from start to done, with ``async`` set."""
    from jax.profiler import ProfileData

    devices, host = {}, {DISPATCH: [], FENCE: []}
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name not in (OPS_LINE, ASYNC_LINE):
                    continue
                is_async = line.name == ASYNC_LINE
                for ev in line.events:
                    text = ev.name
                    name, opcode = parse_instruction(text)
                    kind = op_kind(text, opcode)
                    if is_async and kind != "collective":
                        continue    # copies and slices in flight: DMA, not ops
                    ops.append({"name": name, "opcode": opcode, "kind": kind,
                                "start": ev.start_ns,
                                "end": ev.start_ns + ev.duration_ns,
                                "async": is_async})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in host:
                        host[ev.name].append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
    for spans in host.values():
        spans.sort()
    return {"devices": devices, "host": host}


# ---------------------------------------------------------------- reduction

def op_label(op, scope, op_name):
    """The name an op is summed under in the breakdown: its kind, its scope
    and the end of its ``op_name`` (its instruction name where the module text
    gave none), with numbers taken out so that the same op of every layer and
    every step is one entry."""
    what = "/".join(op_name.split("/")[-3:]) if op_name else op["name"]
    what = re.sub(r"\d+", "N", what)[-90:]
    head = op["kind"] if op["kind"] != "compute" else (op["opcode"] or "op")
    return " ".join(x for x in (head, scope and f"[{scope}]", what) if x)


def reduce_device(ops, window, scope_of):
    """Seconds of one device inside ``window``: busy (union), collective and
    its exposed part, kernel, and compute split by scope. ``scope_of`` maps an
    instruction name to ``(scope, op_name)``."""
    lo, hi = window
    compute, kernels, collectives = [], [], []
    by_scope, by_op = {}, {}
    for op in ops:
        span = clip([(op["start"], op["end"])], lo, hi)
        if not span:
            continue
        (s, e), = span
        if op["kind"] == "collective":
            # On "XLA Ops" an asynchronous collective shows as its -start and
            # -done halves; its interval is the span on "Async XLA Ops".
            if op["async"] or not (op["opcode"] or "").endswith(
                    ("-start", "-done")):
                collectives.append((s, e))
                label = op_label(op, *scope_of.get(op["name"], ("", "")))
                by_op[label] = by_op.get(label, 0) + (e - s)
            continue
        scope, op_name = scope_of.get(op["name"], ("", ""))
        if op["kind"] == "kernel":
            kernels.append((s, e))
        else:
            compute.append((s, e))
            by_scope[scope] = by_scope.get(scope, 0) + (e - s)
        label = op_label(op, scope, op_name)
        by_op[label] = by_op.get(label, 0) + (e - s)
    working = union(compute + kernels)
    coll = union(collectives)
    busy = union(working + coll)
    ns = 1e-9
    return {
        "busy_s": length(busy) * ns,
        "collective_s": sum(e - s for s, e in collectives) * ns,
        "collective_exposed_s": length(subtract(coll, working)) * ns,
        "kernel_s": sum(e - s for s, e in kernels) * ns,
        "compute_s": {k: v * ns for k, v in by_scope.items()},
        "ops": sorted(([k, v * ns] for k, v in by_op.items()),
                      key=lambda kv: -kv[1]),
        "idle": subtract([(lo, hi)], busy),
    }


def host_state(gap, host):
    """What the host was in for most of ``gap``: dispatch, fence or other."""
    shares = {"host in fence (loss read)": covered(gap, union(host[FENCE])),
              "host in dispatch": covered(gap, union(host[DISPATCH]))}
    shares["host in neither (loop, Python)"] = (
        gap[1] - gap[0] - sum(shares.values()))
    return max(shares, key=shares.get)


def reduce(trace, steps, scope_of=None, top=10):
    """The numbers of a traced window of ``steps`` steps, or ``None`` when the
    trace holds no device plane or no benchmark annotation (a CPU trace)."""
    host = trace["host"]
    if not trace["devices"] or not host[DISPATCH] or not host[FENCE]:
        return None
    window = (host[DISPATCH][0][0], max(e for _, e in host[FENCE]))
    per_device = {name: reduce_device(ops, window, scope_of or {})
                  for name, ops in sorted(
                      trace["devices"].items(),
                      key=lambda kv: int(DEVICE_PLANE.match(kv[0]).group(1)))}
    used = {n: d for n, d in per_device.items() if d["busy_s"] > 0}
    if not used:
        return None
    first = next(iter(used))
    slowest = max(used, key=lambda n: used[n]["busy_s"])
    gaps = {}
    for gap in used[first]["idle"]:
        state = host_state(gap, host)
        gaps[state] = gaps.get(state, 0) + (gap[1] - gap[0]) * 1e-9
    return {
        "window_s": (window[1] - window[0]) * 1e-9,
        "steps": steps,
        "busy_s": sum(d["busy_s"] for d in used.values()) / len(used),
        "first": first, "slowest": slowest,
        "devices": {n: {k: v for k, v in d.items() if k not in ("ops", "idle")}
                    for n, d in used.items()},
        "breakdown": {
            "device_ops": used[first]["ops"][:top],
            "idle_gaps": sorted(([k, v] for k, v in gaps.items()),
                                key=lambda kv: -kv[1])[:top],
        },
    }
