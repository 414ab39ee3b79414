"""Reduction of a JAX profiler trace to the benchmark's device numbers.

Reads the ``.xplane.pb`` a ``jax.profiler`` trace writes, through
``jax.profiler.ProfileData`` and nothing else, and gives:

* ``busy_s``: the union of the intervals in which an operation ran on
  a TPU core (the ``XLA Ops`` line, without the control-flow ops
  ``while``, ``conditional`` and ``call``, whose events span the ops
  they run), averaged over the chips traced;
* per-kernel device time: count and seconds of the op events whose HLO
  text matches a kernel's pattern (on a TPU a Pallas call is the
  ``custom-call`` instruction named after the jitted function around
  it, e.g. ``%_edge_spmm_blocked.16 = ... custom-call(...)``), and
  ``feed_s``, the seconds of the ops that make its operands (the
  gather of the rows it reads and their pad, say): followed back from
  the kernel's operands through every op that ran, up to another
  custom-call or a loop, inside the spans of the programs named in
  ``modules``;
* per-program device time: count and seconds of the ``XLA Modules``
  events whose name starts with a jitted function's module name
  (``jit_tick``);
* ``device_ops``: the ten ops with the most device time, each named
  ``%name opcode`` (:func:`op_label`);
* ``idle_gaps``: the ten longest gaps between busy intervals on the
  first chip, each named by the host activity that overlapped it most
  (the benchmark's own ``bench.*`` spans first).
"""
from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
BENCH_SPAN_PREFIX = "bench."
TOP = 10
CONTAINER_OP = re.compile(r"[)}] (while|conditional|call)\(")
# "%fusion.7 = f32[8,6]{1,0:T(8,128)} fusion(...": the instruction's
# name, then its opcode, the first lower-case word after a space that
# opens a parenthesis
HLO_OP = re.compile(r"^(%\S+) = .*?\s([a-z][a-z0-9\-]*)\(")
OPERAND = re.compile(r"%[^\s,(){}]+")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def op_label(hlo: str) -> str:
    """``%name opcode`` of an op event's HLO text (the whole text when it
    does not parse): ``%_edge_spmm_blocked.16 custom-call``."""
    m = HLO_OP.match(hlo)
    return f"{m.group(1)} {m.group(2)}" if m else hlo[:120]


def operands(hlo: str) -> set[str]:
    """Names of the instructions an op event's HLO text reads."""
    m = HLO_OP.match(hlo)
    if not m:
        return set()
    return set(OPERAND.findall(hlo[m.end(2):])) - {m.group(1)}


def _feed_names(ops: dict[str, str], kernel_names: set[str]) -> set[str]:
    """Instruction names of the ops (``ops``: name -> HLO text of the ops
    that ran) whose results flow into the kernels' operands, through
    ops other than custom-calls and loops."""
    todo = set().union(*(operands(ops[k]) for k in kernel_names))
    feed: set[str] = set()
    while todo:
        name = todo.pop()
        hlo = ops.get(name)
        if name in feed or hlo is None or CONTAINER_OP.search(hlo):
            continue
        if HLO_OP.match(hlo).group(2) == "custom-call":
            continue
        feed.add(name)
        todo |= operands(hlo)
    return feed


def merge_intervals(intervals):
    """Sorted union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def reduce_profile(pd, kernels: dict[str, str],
                   modules: dict[str, str]) -> dict:
    """Device numbers of a :class:`jax.profiler.ProfileData`.

    ``kernels`` maps a result name to a regular expression searched in
    the op event's name (its HLO text); ``modules`` maps a result name
    to a prefix of the ``XLA Modules`` event name.  Times are seconds.
    A kernel's ``feed_s`` is counted inside the spans of those modules
    only, where instruction names are the modules' own.
    """
    patterns = {k: re.compile(p) for k, p in kernels.items()}
    kern = {k: {"count": 0, "total_s": 0.0, "feed_s": 0.0, "feed_ops": []}
            for k in kernels}
    mods = {m: {"count": 0, "total_s": 0.0} for m in modules}
    op_time: dict[str, float] = {}
    busy_per_chip = []
    first_busy = None
    match_cache: dict[str, list[str] | None] = {}
    device_planes = [p for p in pd.planes if DEVICE_PLANE.match(p.name)]
    for plane in sorted(device_planes, key=lambda p: p.name):
        intervals = []
        plane_ops = []  # (HLO text, start, duration)
        module_spans = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                for ev in line.events:
                    name = ev.name
                    hits = match_cache.get(name)
                    if hits is None:
                        hits = (None if CONTAINER_OP.search(name) else
                                [k for k, p in patterns.items()
                                 if p.search(name)])
                        match_cache[name] = hits
                    if hits is None:  # a loop's span is not its own work
                        continue
                    s, d = ev.start_ns, ev.duration_ns
                    intervals.append((s, s + d))
                    plane_ops.append((name, s, d))
                    op_time[name] = op_time.get(name, 0.0) + d * 1e-9
                    for k in hits:
                        kern[k]["count"] += 1
                        kern[k]["total_s"] += d * 1e-9
            elif line.name == MODULES_LINE:
                for ev in line.events:
                    for m, prefix in modules.items():
                        if ev.name.startswith(prefix):
                            module_spans.append(
                                (ev.start_ns, ev.start_ns + ev.duration_ns))
                            mods[m]["count"] += 1
                            mods[m]["total_s"] += ev.duration_ns * 1e-9
        _add_feeds(kern, patterns, plane_ops, merge_intervals(module_spans))
        merged = merge_intervals(intervals)
        busy_per_chip.append(sum(e - s for s, e in merged) * 1e-9)
        if first_busy is None:
            first_busy = merged
    if not busy_per_chip:
        raise ValueError("the trace holds no TPU device plane")
    gaps = [(first_busy[i][1], first_busy[i + 1][0])
            for i in range(len(first_busy) - 1)]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    by_op: dict[str, float] = {}
    for name, t in op_time.items():
        short = op_label(name)
        by_op[short] = by_op.get(short, 0.0) + t
    return {
        "chips": len(busy_per_chip),
        "busy_s": sum(busy_per_chip) / len(busy_per_chip),
        "kernels": kern,
        "modules": mods,
        "device_ops": sorted(([n, t] for n, t in by_op.items()),
                             key=lambda x: -x[1])[:TOP],
        "idle_gaps": _name_gaps(pd, gaps),
    }


def _add_feeds(kern, patterns, plane_ops, spans) -> None:
    """Add one chip's feeding ops to each kernel's ``feed_s`` and
    ``feed_ops`` ([label, seconds], most first).  ``spans`` are the
    chip's merged module spans."""
    starts = [a for a, _ in spans]
    inside = []
    for name, s, d in plane_ops:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < spans[i][1]:
            inside.append((name, d))
    ops = {}
    for name, _ in inside:
        m = HLO_OP.match(name)
        if m:
            ops[m.group(1)] = name
    for k, p in patterns.items():
        kernel_names = {i for i, hlo in ops.items() if p.search(hlo)}
        if not kernel_names:
            continue
        feed = _feed_names(ops, kernel_names)
        by_label = dict(kern[k]["feed_ops"])
        for name, d in inside:
            m = HLO_OP.match(name)
            if m and m.group(1) in feed:
                label = op_label(name)
                by_label[label] = by_label.get(label, 0.0) + d * 1e-9
                kern[k]["feed_s"] += d * 1e-9
        kern[k]["feed_ops"] = sorted(([n, t] for n, t in by_label.items()),
                                     key=lambda x: -x[1])


def _name_gaps(pd, gaps) -> list:
    """[[host activity, gap seconds]] for each gap, longest first."""
    if not gaps:
        return []
    # per gap and kind: (overlap, -duration, name); among equal
    # overlaps the shorter (innermost) event wins
    none = (0, 0, "host idle")
    best = [{"bench": none, "other": none} for _ in gaps]
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                for i, (g0, g1) in enumerate(gaps):
                    ov = min(e, g1) - max(s, g0)
                    if ov <= 0:
                        continue
                    kind = ("bench" if ev.name.startswith(BENCH_SPAN_PREFIX)
                            else "other")
                    cand = (ov, -ev.duration_ns, ev.name)
                    if cand[:2] > best[i][kind][:2]:
                        best[i][kind] = cand
    out = []
    for (g0, g1), b in zip(gaps, best):
        length = g1 - g0
        pick = b["bench"] if b["bench"][0] >= 0.5 * length else b["other"]
        out.append([pick[2], length * 1e-9])
    return out

