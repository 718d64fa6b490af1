"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

  window     the host span ``bench.window`` that a run opens and closes
             around the measured window (the first one in the trace);
  busy       per device plane, the union of the intervals in which an XLA
             operation ran, clipped to the window; ``busy_s`` is its mean
             over the device planes;
  programs   per compiled program (the ``XLA Modules`` line, ids stripped),
             its executions and device seconds inside the window;
  ops        device self-seconds per operation inside the window, pro rata where it
             straddles an edge (an
             operation that contains others on its line, as a loop contains
             its body, is charged only for the time its children leave),
             named ``<program>/<HLO op>``;
  idle gaps  each interval of the window in which a device ran nothing,
             labelled by the innermost ``bench.*`` host span that covers
             its midpoint (``host`` where none does), summed per label.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import re
from collections import defaultdict
from pathlib import Path

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_ID_SUFFIX = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir) -> Path:
    paths = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return Path(paths[-1])


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def _self_times(events):
    """(event, self_ns) for events that may nest on one line."""
    order = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    child = [0] * len(order)
    stack = []
    for i, (_, s, d) in enumerate(order):
        while stack and order[stack[-1]][1] + order[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            child[stack[-1]] += d
        stack.append(i)
    return [(ev, max(ev[2] - c, 0)) for ev, c in zip(order, child)]


def _hlo_op(name: str) -> str:
    return name.split(" = ", 1)[0].lstrip("%")


def reduce_planes(planes) -> dict:
    """``planes``: iterable of (plane_name, [(line_name, [(event_name,
    start_ns, duration_ns), ...]), ...])."""
    spans, devices = [], []
    for pname, lines in planes:
        if pname.startswith(DEVICE_PREFIX):
            devices.append(dict(lines))
            continue
        for _, events in lines:
            spans.extend((n, s, s + d) for n, s, d in events
                         if n.startswith(SPAN_PREFIX))
    windows = sorted((s, e) for n, s, e in spans if n == WINDOW_SPAN)
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    lo, hi = windows[0]
    window_ns = hi - lo

    busy_ns, gaps = [], []
    programs = defaultdict(lambda: [0, 0.0])
    ops = defaultdict(float)
    for lines in devices:
        modules = sorted((s, s + d, _ID_SUFFIX.sub("", n))
                         for n, s, d in lines.get(MODULES_LINE, []))
        mod_starts = [m[0] for m in modules]
        op_events = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        busy = []
        for (name, s, d), own in _self_times(op_events):
            cs, ce = _clip(s, s + d, lo, hi)
            if ce <= cs:
                continue
            busy.append((cs, ce))
            j = bisect.bisect_right(mod_starts, s) - 1
            prog = modules[j][2] if j >= 0 and modules[j][1] > s else "?"
            ops[f"{prog}/{_hlo_op(name)}"] += \
                own * (ce - cs) / d / 1e9
        merged = _union(busy)
        busy_ns.append(sum(e - s for s, e in merged))
        prev = lo
        for s, e in merged + [[hi, hi]]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        for name, s, d in lines.get(MODULES_LINE, []):
            cs, ce = _clip(s, s + d, lo, hi)
            if ce > cs:
                p = programs[_ID_SUFFIX.sub("", name)]
                p[0] += 1
                p[1] += (ce - cs) / 1e9

    # innermost covering span: the latest-starting one that still covers
    inner = sorted((s, e, n) for n, s, e in spans if n != WINDOW_SPAN)
    starts = [s for s, _, _ in inner]
    idle = defaultdict(float)
    for s, e in gaps:
        mid = (s + e) / 2
        label = "host"
        for j in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
            if inner[j][1] >= mid:
                label = inner[j][2][len(SPAN_PREFIX):]
                break
        idle[label] += (e - s) / 1e9 / len(devices)

    def top(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                ][:10]

    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "devices": len(devices),
        "programs": {k: {"count": c, "seconds": t}
                     for k, (c, t) in programs.items()},
        "breakdown": {"device_ops": top(ops), "idle_gaps": top(idle)},
    }


def read_xplane(path):
    """The planes of an ``.xplane.pb`` in the form ``reduce_planes`` takes."""
    from jax.profiler import ProfileData
    path = Path(path)
    if path.suffix == ".gz":
        data = ProfileData.from_serialized_xspace(
            gzip.decompress(path.read_bytes()))
    else:
        data = ProfileData.from_file(str(path))
    return [(p.name, [(ln.name, [(e.name, e.start_ns, e.duration_ns)
                                 for e in ln.events]) for ln in p.lines])
            for p in data.planes]


def reduce_trace(trace_dir) -> dict:
    return reduce_planes(read_xplane(find_xplane(trace_dir)))
