"""What every runner shares: finding a cell's files by the names in
`BENCHMARK.json`, the device check, host spans, the traced stretch, the
per-layer metric readers and the result line. It knows no model, cell or
metric by name.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")


class CellError(Exception):
    """The cell cannot run as asked (no chip, unknown name, bad file)."""


def read_json(*parts):
    with open(os.path.join(ROOT, *parts)) as fh:
        return json.load(fh)


def load_module(kind: str, name: str):
    """The file `benchmark/<kind>/<name>.py`, whatever characters of a name
    `name` holds (metric names have dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise CellError(f"no {kind} file {path}")
    modname = "benchmark." + kind + "." + name.replace(".", "_")
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str) -> dict:
    """The cell's entry with its configuration and traffic files read in."""
    spec = read_json("BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise CellError(f"unknown workload {workload!r}; BENCHMARK.json has "
                        f"{sorted(cells)}")
    cell = dict(cells[workload])
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cell["config_file"] = read_json(entry["file"])
    cell["traffic_file"] = read_json("benchmark", "traffic",
                                     cell["traffic"] + ".json")
    cell["spec"] = spec
    return cell


def metric_names(cell: dict, group: str) -> list:
    """Metrics of `group` (`end_to_end` / `per_layer`) this cell reports: those
    that list it, and those that list no cells, which every cell reports (a
    per-layer metric: every cell that reports the end-to-end metric it
    moves)."""
    spec = cell["spec"]
    e2e = [m["name"] for m in spec["end_to_end"]
           if "workloads" not in m or cell["name"] in m["workloads"]]
    if group == "end_to_end":
        return e2e
    return [m["name"] for m in spec["per_layer"]
            if (cell["name"] in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def unit_of(cell: dict, name: str) -> str:
    for group in ("end_to_end", "per_layer"):
        for m in cell["spec"][group]:
            if m["name"] == name:
                return m["unit"]
    raise KeyError(name)


def devices_for(cell: dict, rehearsal: bool):
    """The chips the cell runs on. Anything but that many TPU chips is an
    error, unless this is a rehearsal (tests on the CPU)."""
    import jax

    devices = jax.devices()
    if rehearsal:
        return devices[:cell["chips"]]
    if devices[0].platform != "tpu":
        raise CellError(f"JAX found {devices[0].platform} devices and no "
                        f"TPU; the benchmark measures only on the chip")
    if len(devices) < cell["chips"]:
        raise CellError(f"cell {cell['name']} needs {cell['chips']} chips, "
                        f"JAX found {len(devices)}")
    return devices[:cell["chips"]]


def peaks_for(device_kind: str) -> dict:
    table = read_json("benchmark", "peaks.json")["by_device_kind"]
    if device_kind not in table:
        raise CellError(f"device_kind {device_kind!r} is not in "
                        f"benchmark/peaks.json; add its row with its source")
    return table[device_kind]


def memory_peak_bytes(devices) -> int:
    """Peak on the fullest chip, read when the window has closed. On this
    runtime the allocator's `peak_bytes_in_use` counts live arrays only; the
    temporaries of the loaded programs are booked apart, under
    `bytes_reserved` (0 before the step program first runs, the compiler's
    own count of its temporaries after, 0 again once the programs are freed:
    PERF.md has the readings). So the peak is the larger of the arrays' own
    peak and what the chip holds at the close: live arrays plus that
    reserve."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        held = (int(stats.get("bytes_in_use", 0))
                + int(stats.get("peak_bytes_reserved", 0)))
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)), held)
    return peak


def quantile(values, q: float) -> float:
    """Linear-interpolated quantile of a non-empty list (q in [0, 1])."""
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


class Spans:
    """Host-clock spans recorded round the calls into each layer, from
    outside the program. While a trace is being taken each span is also a
    `TraceAnnotation`, so the profiler's own clock carries it and idle gaps
    on the device can be laid against it."""

    def __init__(self):
        self.spans: dict = {}
        self.annotate = False

    def __call__(self, name: str):
        return _Span(self, name)

    def durations(self, name: str) -> list:
        return [b - a for a, b in self.spans.get(name, [])]


class _Span:
    def __init__(self, owner: Spans, name: str):
        self.owner, self.name, self.ann = owner, name, None

    def __enter__(self):
        if self.owner.annotate:
            import jax
            self.ann = jax.profiler.TraceAnnotation("bench:" + self.name)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        self.owner.spans.setdefault(self.name, []).append((self.t0, t1))
        return False


class Tracer:
    """Takes the profiler's trace of a stretch inside the window: `after_s`
    into it, for `for_s`, ended at the runner's next check. Starting and
    stopping the profiler each stall the host for seconds. A training window
    only pauses; a serving window would queue the requests due meanwhile, so
    its runner starts the profiler `lead_s` before the stretch and stops it
    when the window has closed (`hold`)."""

    def __init__(self, spans: Spans, enabled: bool, after_s: float = 1.0,
                 for_s: float = 3.0, lead_s: float = 0.0, hold: bool = False):
        self.spans, self.enabled = spans, enabled
        self.after_s, self.for_s = after_s, for_s
        self.lead_s, self.hold = lead_s, hold
        self.dir = os.path.join(ROOT, ".cache", "bench_trace")
        self.state = "off" if not enabled else "waiting"
        self.t_start = None
        self.units = 0        # units of work dispatched inside the stretch
        self.stretch_s = 0.0  # its length on the host's clock

    def add(self, n: int) -> None:
        if self.state == "tracing":
            self.units += n

    def poll(self, elapsed_s: float, drain) -> None:
        """Called by the runner between units of work, with the seconds since
        the window opened (negative before). `drain()` waits for everything
        dispatched, so the traced stretch holds whole work."""
        import jax

        if (self.state == "waiting"
                and elapsed_s >= self.after_s - self.lead_s):
            drain()
            shutil.rmtree(self.dir, ignore_errors=True)
            jax.profiler.start_trace(self.dir)
            self.state = "started"
        if self.state == "started" and elapsed_s >= self.after_s:
            drain()
            self.spans.annotate = True
            self.state, self.t_start = "tracing", time.perf_counter()
        elif (self.state == "tracing"
              and time.perf_counter() - self.t_start >= self.for_s):
            self._end_stretch(drain)
            if not self.hold:
                self.finish(drain)

    def _end_stretch(self, drain) -> None:
        drain()
        self.stretch_s = time.perf_counter() - self.t_start
        self.spans.annotate = False
        self.state = "stretched"

    def finish(self, drain) -> None:
        """Stop the profiler; a stretch still open ends here."""
        import jax

        if self.state == "tracing":
            self._end_stretch(drain)
        if self.state in ("started", "stretched"):
            jax.profiler.stop_trace()
            self.state = "done" if self.stretch_s else "off"

    def reduced(self):
        from benchmark import trace
        if self.state != "done":
            return None
        out = trace.reduce(trace.load(self.dir))
        shutil.rmtree(self.dir, ignore_errors=True)
        return out


def per_layer(cell: dict, ctx: dict) -> dict:
    """Each per-layer metric of the cell, from its own reader file. A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for name in metric_names(cell, "per_layer"):
        value = load_module("metrics", name).read(ctx)
        if value is not None:
            out[name] = {"value": float(value), "unit": unit_of(cell, name)}
    return out


def emit(cell, *, trace_on, e2e, ctx, attempted, failed, devices, memory_peak,
         checks, correct, reduced=None):
    """Print the compared numbers on standard error and the result line as
    the last line of standard output."""
    if trace_on:
        metrics = per_layer(cell, ctx)
    else:
        metrics = {k: {"value": float(v), "unit": unit_of(cell, k)}
                   for k, v in e2e.items()
                   if k in metric_names(cell, "end_to_end")}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(memory_peak)}
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if trace_on and reduced is not None:
        from benchmark import trace
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = trace.breakdown(reduced)
    line["compared"] = {name: {"value": value, "limit": limit}
                        for name, value, limit in checks}
    sys.stdout.flush()
    for name, value, limit in checks:
        print(f"compared {name}: {value!r} limit {limit!r}", file=sys.stderr)
    print(f"correct: {bool(correct)}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return line
