"""Reduce a profiler trace of the measured window to metrics.

The profiler's trace (the ``*.trace.json.gz`` it writes beside the
``.xplane.pb``) is first turned into plain data (``load``): planes, each
with lines of events ``{"name", "start_ns", "dur_ns", "stats"}``.  Device
planes are those named ``/device:<PLATFORM>:<i>``; their ops are the events
of the line ``XLA Ops`` that hold no other op (a ``while`` op spans the
ops of its body and is left out).  The harness's own host spans
(``bench.window`` around the whole window, ``bench.call`` around each grid
call and ``bench.block`` around each wait) are events of the host plane,
on the same clock.

* busy time of a device: the union of its op intervals inside the window;
  idle share = 1 - busy / window;
* ``device_ops``: op time inside the window summed by op, under the op's
  name and the JAX name stack (``tf_op``) of its metadata;
* ``idle_gaps``: the longest stretches inside the window in which no op
  ran on a device, each named by the innermost harness span the host was
  in at the middle of the gap.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:([A-Z]+):(\d+)$")
# op metadata that names the JAX code an op came from
NAME_STACK_STATS = ("tf_op",)


def load(profile_dir: str) -> dict:
    """The trace the profiler wrote under ``profile_dir``, as plain data."""
    paths = glob.glob(os.path.join(profile_dir, "**", "*.trace.json.gz"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"want one *.trace.json.gz under "
                                f"{profile_dir}, found {paths}")
    with gzip.open(paths[0], "rt") as f:
        events = json.load(f)["traceEvents"]
    process, thread = {}, {}
    for e in events:
        if e.get("ph") == "M" and e["name"] == "process_name":
            process[e["pid"]] = e["args"]["name"]
        elif e.get("ph") == "M" and e["name"] == "thread_name":
            thread[(e["pid"], e["tid"])] = e["args"]["name"]
    planes: dict = {}
    for e in events:
        if e.get("ph") != "X" or e["pid"] not in process:
            continue
        lines = planes.setdefault(process[e["pid"]], {})
        lines.setdefault(thread.get((e["pid"], e["tid"]), str(e["tid"])),
                         []).append({
            "name": e["name"], "start_ns": e["ts"] * 1e3,
            "dur_ns": e.get("dur", 0.0) * 1e3,
            "stats": {k: v for k, v in e.get("args", {}).items()
                      if k in NAME_STACK_STATS}})
    return {"planes": [{"name": p, "lines": [{"name": n, "events": evs}
                                             for n, evs in lines.items()]}
                       for p, lines in planes.items()]}


def _host_spans(trace: dict) -> list:
    return [(e["name"], e["start_ns"], e["start_ns"] + e["dur_ns"])
            for p in trace["planes"] if p["name"].startswith("/host:")
            for line in p["lines"] for e in line["events"]
            if e["name"].startswith("bench.")]


def window(trace: dict) -> tuple[float, float]:
    spans = [(s, e) for name, s, e in _host_spans(trace) if name == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"trace holds {len(spans)} {WINDOW_SPAN} spans, "
                         f"want 1")
    return spans[0]


def device_ops(trace: dict) -> dict:
    """{device plane name: [(start_ns, end_ns, label), ...]}"""
    out = {}
    for p in trace["planes"]:
        if not DEVICE_PLANE.match(p["name"]):
            continue
        ops = []
        for line in p["lines"]:
            if line["name"] != OPS_LINE:
                continue
            events = sorted(line["events"],
                            key=lambda e: (e["start_ns"], -e["dur_ns"]))
            for i, e in enumerate(events):
                end = e["start_ns"] + e["dur_ns"]
                if i + 1 < len(events) and events[i + 1]["start_ns"] < end:
                    continue        # holds the next op: a control-flow op
                stack = next((e["stats"][k] for k in NAME_STACK_STATS
                              if e["stats"].get(k)), "")
                ops.append((e["start_ns"], end,
                            f"{e['name']} {stack}".strip()))
        out[p["name"]] = sorted(ops)
    return out


def _union(intervals, lo, hi) -> list:
    """Merged [start, end) intervals clipped to [lo, hi]."""
    merged = []
    for s, e, *_ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _innermost(spans, t) -> str:
    inside = [(e - s, name) for name, s, e in spans if s <= t <= e]
    return min(inside)[1] if inside else "outside harness spans"


def reduce(trace: dict, top: int = 10) -> dict:
    """Window, per-device busy time and idle share, and the breakdown."""
    lo, hi = window(trace)
    win_ns = hi - lo
    ops = device_ops(trace)
    spans = _host_spans(trace)
    busy, totals, gaps = {}, {}, []
    for dev, dev_ops in ops.items():
        merged = _union(dev_ops, lo, hi)
        busy[dev] = sum(e - s for s, e in merged) / 1e9
        for s, e, label in dev_ops:
            clipped = min(e, hi) - max(s, lo)
            if clipped > 0:
                totals[label] = totals.get(label, 0.0) + clipped / 1e9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, _innermost(spans, (s + e) / 2)))
    gaps.sort(reverse=True)
    return {
        "window_s": win_ns / 1e9,
        "busy_s": busy,
        "idle_share": {d: 1.0 - b / (win_ns / 1e9) for d, b in busy.items()},
        "device_ops": sorted(([k, v] for k, v in totals.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[name, ns / 1e9] for ns, name in gaps[:top]],
    }
