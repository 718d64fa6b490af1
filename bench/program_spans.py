"""The program's own spans (``repro.*``, written by ``repro.obs.span`` on
the profiler's clock) over the traced window, and the device idle time
each one holds.

For each span name:

  count     spans that overlap the window;
  seconds   their time inside the window;
  self_s    the part of it that no child ``repro.*`` span covers: the
            window is cut into pieces, each held by the innermost span
            over it (the latest-starting one still open), and a span's
            self time is the pieces it holds;
  idle_s    the device idle time inside those pieces, in seconds per
            device: an idle interval that crosses a span boundary is
            split at it, each part charged to the span that holds it.

Idle time and window time under no program span go to ``(outside)``, so
the names' ``idle_s`` add up to the window's whole idle time.  A trace
without program spans (a program that writes none) reduces to ``{}``.

The window and the device's busy time are ``bench/trace_reduce.py``'s.
That reduction does not carry the program's spans, so the cells' result
lines do not either; one traced run of a cell with them:

    python3 bench/program_spans.py --workload <cell> --seed <n> --seconds <s>

prints ``bench/run.py``'s result line, then one line with the layers'
readings (``readings``) and the spans' reduction.
"""
from __future__ import annotations

import bisect
import json
import sys
from collections import defaultdict
from pathlib import Path

PREFIX = "repro."
OUTSIDE = "(outside)"
PROMOTE = "repro.ft.recover.promote"
# layer -> the prefix of its spans' names
LAYERS = {"entry_points": "repro.serve.", "ft_driver": "repro.ft.",
          "workload": "repro.workload."}


def span_name(name: str) -> str:
    """The span's name without metadata that an older profiler encodes
    into it (``name#key=value#``)."""
    return name.split("#", 1)[0]


def innermost_pieces(spans, lo, hi):
    """[(start, end, name)]: ``[lo, hi)`` cut where any span starts or
    ends, each piece named by the innermost span over it (``OUTSIDE``
    where none is).  ``spans``: [(name, start, end)]."""
    pieces, stack = [], []
    t = lo

    def cut(until, name):
        nonlocal t
        s, e = max(t, lo), min(until, hi)
        if e > s:
            pieces.append((s, e, name))
        t = max(t, until)

    def close(until):
        # close every open span that ends by ``until``, innermost first;
        # one that a later-starting span outlasted holds nothing more
        while stack and stack[-1][2] <= until:
            name, _, end = stack.pop()
            if end > t:
                cut(end, name)

    for name, s, e in sorted(spans, key=lambda sp: (sp[1], -sp[2])):
        close(s)
        cut(s, stack[-1][0] if stack else OUTSIDE)
        stack.append((name, s, e))
    close(float("inf"))
    cut(hi, OUTSIDE)
    return pieces


def reduce_spans(spans, gaps, lo, hi, n_devices: int) -> dict:
    """``spans``: [(name, start_ns, end_ns)] of the program's spans;
    ``gaps``: [(start_ns, end_ns)] of device idle time, over all devices;
    ``[lo, hi)`` the window."""
    if not spans:
        return {}
    out = defaultdict(lambda: {"count": 0, "seconds": 0.0, "self_s": 0.0,
                               "idle_s": 0.0})
    for name, s, e in spans:
        cs, ce = max(s, lo), min(e, hi)
        if ce > cs:
            out[name]["count"] += 1
            out[name]["seconds"] += (ce - cs) / 1e9
    pieces = innermost_pieces(spans, lo, hi)
    starts = [p[0] for p in pieces]
    for s, e, name in pieces:
        out[name]["self_s"] += (e - s) / 1e9
    for gs, ge in gaps:
        j = max(bisect.bisect_right(starts, gs) - 1, 0)
        while j < len(pieces) and pieces[j][0] < ge:
            ps, pe, name = pieces[j]
            overlap = min(pe, ge) - max(ps, gs)
            if overlap > 0:
                out[name]["idle_s"] += overlap / 1e9 / n_devices
            j += 1
    return dict(out)


def reduce_planes(planes) -> dict:
    """``planes`` as ``trace_reduce.reduce_planes`` takes them: the
    window's length, its idle seconds per device, and the program's spans
    reduced over it (``program``)."""
    from bench import trace_reduce as tr
    window, spans, devices = [], [], []
    for pname, lines in planes:
        if pname.startswith(tr.DEVICE_PREFIX):
            devices.append(dict(lines))
            continue
        for _, events in lines:
            window.extend((s, s + d) for n, s, d in events
                          if n == tr.WINDOW_SPAN)
            spans.extend((span_name(n), s, s + d) for n, s, d in events
                         if n.startswith(PREFIX))
    if not window:
        raise ValueError(f"no {tr.WINDOW_SPAN} span in the trace")
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    lo, hi = min(window)
    gaps = []
    for lines in devices:
        events = lines.get(tr.OPS_LINE) or lines.get(tr.MODULES_LINE) or []
        busy = [tr._clip(s, s + d, lo, hi) for _, s, d in events]
        prev = lo
        for s, e in tr._union(b for b in busy if b[1] > b[0]) + [[hi, hi]]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
    return {"window_s": (hi - lo) / 1e9,
            "idle_s": sum(e - s for s, e in gaps) / 1e9 / len(devices),
            "program": reduce_spans(spans, gaps, lo, hi, len(devices))}


def idle_share(reduction: dict, prefix: str):
    """100 x the idle seconds charged to the spans named ``prefix*`` over
    the window; None where the trace holds no such span."""
    spans = [v for k, v in reduction["program"].items()
             if k.startswith(prefix)]
    if not spans or reduction["window_s"] <= 0:
        return None
    return 100.0 * sum(v["idle_s"] for v in spans) / reduction["window_s"]


def promote_ms(reduction: dict):
    """Mean length of one ``repro.ft.recover.promote`` span in the
    window, in ms; None where there is none."""
    span = reduction["program"].get(PROMOTE)
    if not span or not span["count"]:
        return None
    return 1e3 * span["seconds"] / span["count"]


def readings(reduction: dict) -> dict:
    """Each layer's share of the window in which the device idled under
    its spans (``idle_share.<layer>``, %), the same for ``(outside)`` and
    for the whole window (``idle_share.serve``'s number), and
    ``promote_ms``."""
    out = {f"idle_share.{layer}": idle_share(reduction, prefix)
           for layer, prefix in LAYERS.items()}
    out["idle_share.outside"] = idle_share(reduction, OUTSIDE)
    out["idle_share.all"] = 100.0 * reduction["idle_s"] / \
        reduction["window_s"]
    out["promote_ms"] = promote_ms(reduction)
    return out


def main(argv=None) -> int:
    """One traced run of a cell through ``bench/run.py``, whose trace is
    also reduced here."""
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from bench import run, trace_reduce
    reduce_trace, found = trace_reduce.reduce_trace, {}

    def both(trace_dir):
        planes = trace_reduce.read_xplane(trace_reduce.find_xplane(trace_dir))
        found.update(reduce_planes(planes))
        return trace_reduce.reduce_planes(planes)

    trace_reduce.reduce_trace = both
    try:
        rc = run.main([*(sys.argv[1:] if argv is None else argv),
                       "--trace", "1"])
    finally:
        trace_reduce.reduce_trace = reduce_trace
    if rc == 0:
        print(json.dumps({"program_spans": readings(found),
                          "spans": found["program"]}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
