"""Reduction of a profiler trace to what the per-layer metrics read.

`load` turns the profiler's `.xplane.pb` into a plain structure (`devices`:
per chip the leaf operations as [start_ns, duration_ns, name]; `host`: the
benchmark's own `TraceAnnotation` spans on the same clock), and `reduce` turns
that into the busy union, the idle gaps and the time per operation. The track
selection follows `tools/profile_step.py` (`summarize`): only the device
plane's "XLA Ops" line holds leaf operations; the other lines (modules, steps)
nest them and would count every level again. The union of busy intervals,
the idle share and the gap attribution are this file's own.
"""

from __future__ import annotations

import glob
import os
import re

SPAN_PREFIX = "bench:"


def load(log_dir: str) -> dict:
    """Plain structure of the newest trace under `log_dir`."""
    import jax

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    devices, modules, host, layout = {}, {}, [], []
    for plane in data.planes:
        lines = list(plane.lines)
        layout.append([plane.name, [ln.name for ln in lines]])
        if re.match(r"^/device:TPU:\d+$", plane.name):
            ops = [ln for ln in lines if ln.name == "XLA Ops"]
            devices[plane.name] = [
                [float(e.start_ns), float(e.duration_ns), e.name]
                for ln in ops for e in ln.events]
            modules[plane.name] = [
                [float(e.start_ns), float(e.duration_ns), e.name]
                for ln in lines if ln.name == "XLA Modules"
                for e in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in lines:
                host.extend(
                    [float(e.start_ns), float(e.duration_ns),
                     e.name[len(SPAN_PREFIX):]]
                    for e in ln.events if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "modules": modules, "host": sorted(host),
            "layout": layout}


def _union(intervals):
    """Merged, sorted [start, end] intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _covering_span(host, start, end):
    """Name of the host span that covers most of [start, end]; spans nest, so
    of equal covers the shortest (innermost) wins. None where nothing of the
    benchmark's was running."""
    best, best_key = None, (0.0, 0.0)
    for s, d, name in host:
        cover = min(end, s + d) - max(start, s)
        if cover <= 0:
            continue
        key = (cover, -d)
        if key > best_key:
            best, best_key = name, key
    return best


def reduce(trace: dict, top: int = 10) -> dict:
    """busy_s (mean over chips of the union of operation intervals),
    window_s, per-operation seconds (summed over chips), and the longest idle
    gaps on the first chip with the host span that covers each.

    The window runs from the start of the benchmark's first host span to the
    end of its last; where the trace holds none, from the first device
    operation to the end of the last."""
    devices = {k: v for k, v in trace["devices"].items() if v}
    if not devices:
        return {"busy_s": 0.0, "window_s": 0.0, "per_op": {},
                "per_module": {}, "gaps": [], "chips": 0}
    host = trace.get("host") or []
    if host:
        w0 = min(s for s, _, _ in host)
        w1 = max(s + d for s, d, _ in host)
    else:
        w0 = min(s for ev in devices.values() for s, _, _ in ev)
        w1 = max(s + d for ev in devices.values() for s, d, _ in ev)
    per_op: dict = {}
    per_module: dict = {}
    for events in (trace.get("modules") or {}).values():
        for s, d, name in events:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                per_module[name] = per_module.get(name, 0.0) + (b - a) * 1e-9
    busy, gaps = [], []
    for n, (_, events) in enumerate(sorted(devices.items())):
        clipped = []
        for s, d, name in events:
            a, b = max(s, w0), min(s + d, w1)
            if b <= a:
                continue
            clipped.append((a, b))
            per_op[name] = per_op.get(name, 0.0) + (b - a) * 1e-9
        merged = _union(clipped)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        if n == 0:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    gaps.append((b - a, a, b))
    by_span: dict = {}
    for dur, a, b in gaps:
        name = _covering_span(host, a, b) or "outside_spans"
        by_span[name] = by_span.get(name, 0.0) + dur * 1e-9
    longest = sorted(gaps, reverse=True)[:top]
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": (w1 - w0) * 1e-9,
        "chips": len(devices),
        "per_op": per_op,
        "per_module": per_module,
        "idle_by_span": by_span,
        "gaps": [[_covering_span(host, a, b) or "outside_spans", dur * 1e-9]
                 for dur, a, b in longest],
    }


def idle_share(reduced) -> float | None:
    """Per cent of the traced window in which no operation ran on the device
    (mean over chips); None where there is no device trace to read."""
    if not reduced or not reduced["window_s"] or not reduced["busy_s"]:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])


def op_seconds(reduced: dict, pattern: str, what: str = "per_op") -> float:
    """Device seconds of the operations (or, with `what="per_module"`, of
    the whole programs) whose name matches `pattern`."""
    rx = re.compile(pattern)
    return sum(t for name, t in reduced[what].items() if rx.search(name))


def short_name(op: str) -> str:
    """The trace names a device operation by its whole HLO line. Kept: the
    instruction's name without its number, its opcode, the first array it
    produces and a custom call's target: '%fusion.136 = f32[50257,768]{1,0}
    fusion(...)' -> 'fusion fusion f32[50257,768]'."""
    m = re.match(r"%([\w\-]+?)(?:\.\d+)? = .*?\s([\w\-]+)\(", op)
    if not m:
        return op[:60]
    shape = re.search(r" = \(?(\w+\[[\d,]*\])", op)
    target = re.search(r'custom_call_target="([\w\-]+)"', op)
    return " ".join(filter(None, [m.group(1), m.group(2),
                                  shape and shape.group(1),
                                  target and target.group(1)]))


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The operations that took most device time, summed by short name, and
    the idle time by the host span that covered it."""
    by_name: dict = {}
    for name, t in reduced["per_op"].items():
        key = short_name(name)
        by_name[key] = by_name.get(key, 0.0) + t
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(reduced.get("idle_by_span", {}).items(),
                  key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}
