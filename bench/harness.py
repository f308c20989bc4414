"""What every cell shares: the compile cache and compile counter, host spans,
the check of a cell's chips against its mix's mesh, the profiler slice and
its reduction to device busy time and a breakdown (of each traced chip),
the per-layer metric readers, and the result line.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import importlib.util
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
# a traced run profiles TRACE_SECONDS from this share of its window
TRACE_START = 0.3
TRACE_SECONDS = 4.0


def use_compile_cache(checkout: str) -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` when set, else
    ``<checkout>/.jax_cache`` (a fixed path: the path is part of the key).
    Every program is cached, however short its compile, so the serving
    programs that compile in under a second load too."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        checkout, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileMeter:
    """Backend compiles (cache loads included), seen through
    ``jax.monitoring``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == self.EVENT:
            self.compiles += 1
            self.seconds += duration

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


@dataclasses.dataclass
class Span:
    name: str
    t0: float
    t1: float
    info: dict | None = None

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class Spans:
    """Host spans at the benchmark's calls into each layer.  Each span is
    kept in memory (``perf_counter`` seconds) and written into the profiler's
    trace as a ``TraceAnnotation`` of the same name, so a traced run can say
    what the host did while the device idled."""

    def __init__(self):
        self.spans: list[Span] = []
        self._open = None

    def span(self, name: str, info: dict | None = None):
        return _SpanCtx(self, name, info)

    def open(self, name: str):
        """Start a span that ``close`` ends (for host time between calls)."""
        import jax
        self.close()
        ann = jax.profiler.TraceAnnotation(name)
        ann.__enter__()
        self._open = (name, time.perf_counter(), ann)

    def close(self):
        if self._open is not None:
            name, t0, ann = self._open
            ann.__exit__(None, None, None)
            self.spans.append(Span(name, t0, time.perf_counter()))
            self._open = None

    def named(self, name: str) -> list[Span]:
        return [s for s in self.spans if s.name == name]


class _SpanCtx:
    def __init__(self, spans, name, info):
        self.spans, self.name, self.info = spans, name, info

    def __enter__(self):
        import jax
        self.ann = jax.profiler.TraceAnnotation(self.name)
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.ann.__exit__(*exc)
        self.spans.spans.append(Span(self.name, self.t0, t1, self.info))
        return False


class Profile:
    """One profiled slice, marked in the trace by a ``bench.slice`` span."""

    def __init__(self, directory: str):
        self.dir = directory
        self.t0 = self.t1 = None
        self._ann = None

    def start(self):
        import jax
        jax.profiler.start_trace(self.dir)
        self._ann = jax.profiler.TraceAnnotation("bench.slice")
        self._ann.__enter__()
        self.t0 = time.perf_counter()

    def stop(self):
        import jax
        self.t1 = time.perf_counter()
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()

    @property
    def started(self) -> bool:
        return self.t0 is not None

    @property
    def stopped(self) -> bool:
        return self.t1 is not None


# ------------------------------------------------------------------ chips

def mesh_dims(spec: str) -> tuple[int, ...]:
    """The shape a mix's ``"mesh"`` names: 1 to 3 positive whole numbers
    joined by "x" (``"1x4"``), the program's ``--mesh`` spelling that
    ``repro.launch.mesh.mesh_from_spec`` builds.  Read without touching a
    device."""
    dims = tuple(int(d) if d.isdigit() else 0 for d in spec.split("x"))
    if not 1 <= len(dims) <= 3 or min(dims) < 1:
        raise ValueError(f"mesh {spec!r}: expected 1 to 3 positive whole "
                         "numbers joined by 'x', such as '1x4'")
    return dims


def check_chips(cell: dict, mix: dict) -> str | None:
    """Why a cell's chips and its mix's mesh do not go together, or None.
    A training mix spreads a cell over several chips by its ``"mesh"``, whose
    size must be the cell's ``chips``; a cell on one chip may leave it out.
    A serving cell runs on one chip and takes no mesh."""
    spec = mix.get("mesh")
    if spec is None:
        if cell["chips"] > 1:
            return (f"{cell['chips']} chips, but the mix names no mesh to "
                    "spread the cell over them")
        return None
    if mix["kind"] != "train":
        return "a mesh is a training mix's key; serving runs on one chip"
    try:
        size = math.prod(mesh_dims(spec))
    except ValueError as e:
        return str(e)
    if size != cell["chips"]:
        return (f"the mix's mesh {spec!r} spans {size} chips, the cell asks "
                f"for {cell['chips']}")
    return None


# ---------------------------------------------------------------- the trace

def trace_events(directory: str, chips: int = 1) -> dict:
    """The newest ``.xplane.pb`` under ``directory`` as plain lists:
    ``devices``: for each of the first ``chips`` TPUs, by plane name, its
    ops [(name, start_ns, dur_ns)] from its "XLA Ops" line (its "XLA
    Modules" line where a profiler version names no ops); ``device``: the
    first of them; ``host``: [(name, start_ns, dur_ns)] of every host event
    whose name starts with "bench."."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return {"device": [], "devices": [], "host": []}
    data = ProfileData.from_file(files[-1])
    devices, host = [], []
    tpu_planes = sorted((p for p in data.planes
                         if p.name.startswith("/device:TPU:")),
                        key=lambda p: p.name)
    for plane in tpu_planes[:chips]:
        lines = {line.name: line for line in plane.lines}
        name = "XLA Ops" if "XLA Ops" in lines else "XLA Modules"
        devices.append([(e.name, e.start_ns, e.duration_ns)
                        for e in lines[name].events] if name in lines else [])
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.duration_ns)
                         for e in line.events if e.name.startswith("bench.")]
    return {"device": devices[0] if devices else [], "devices": devices,
            "host": host}


def per_device(events: dict) -> list[dict]:
    """``events`` once for each traced device, with that device's ops as
    ``device``: what ``busy_ns``, ``idle_gaps`` and ``breakdown`` read.  A
    trace recorded with one device's ops alone gives that one."""
    return [{**events, "device": ops}
            for ops in events.get("devices") or [events["device"]]]


def save_events(events: dict, path: str):
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def load_events(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def slice_bounds(events: dict) -> tuple[float, float] | None:
    """(start_ns, end_ns) of the ``bench.slice`` span in the trace."""
    marks = [(s, s + d) for n, s, d in events["host"] if n == "bench.slice"]
    return marks[0] if marks else None


def merged(intervals, lo: float, hi: float):
    """The union of ``(start, end)`` intervals clipped to [lo, hi], as
    sorted disjoint intervals."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(events: dict, lo: float, hi: float) -> float:
    """Nanoseconds of [lo, hi] in which some op ran on the device."""
    return sum(e - s for s, e in merged(
        ((s, s + d) for _, s, d in events["device"]), lo, hi))


def mean_busy_ns(events: dict, lo: float, hi: float) -> float:
    """``busy_ns`` averaged over the traced devices."""
    busy = [busy_ns(dev, lo, hi) for dev in per_device(events)]
    return sum(busy) / len(busy)


def idle_gaps(events: dict, lo: float, hi: float):
    """[(start_ns, end_ns)] of [lo, hi] in which no op ran."""
    gaps, t = [], lo
    for s, e in merged(((s, s + d) for _, s, d in events["device"]), lo, hi):
        if s > t:
            gaps.append((t, s))
        t = e
    if hi > t:
        gaps.append((t, hi))
    return gaps


def breakdown(events: dict, lo: float, hi: float, top: int = 10) -> dict:
    """The device ops that took most time in [lo, hi], and the longest idle
    gaps, each named by the innermost ``bench.*`` host span (other than the
    slice itself) that covers the gap's middle, or "host" where none does."""
    per_op = {}
    for name, s, d in events["device"]:
        e = min(s + d, hi)
        s = max(s, lo)
        if e > s:
            per_op[name] = per_op.get(name, 0.0) + (e - s) / 1e9
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    spans = [(n, s, s + d) for n, s, d in events["host"]
             if n != "bench.slice"]
    gaps = []
    for s, e in sorted(idle_gaps(events, lo, hi),
                       key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) / 2
        cover = [(se - ss, n) for n, ss, se in spans if ss <= mid <= se]
        gaps.append([min(cover)[1] if cover else "host", (e - s) / 1e9])
    return {"device_ops": [[n, v] for n, v in ops], "idle_gaps": gaps}


# ------------------------------------------------------- per-layer metrics

def read_metric(name: str, ctx: dict):
    """Run the reader ``bench/metrics/<name>.py`` on ``ctx``; None when it
    finds nothing to read."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


# ---------------------------------------------------------------- results

def device_info(count: int) -> dict:
    import jax
    devs = jax.devices()[:count]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def peaks(kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["kinds"]
    if kind not in table:
        raise ValueError(f"no published peaks for device kind {kind!r}; "
                         f"known: {sorted(table)}")
    return table[kind]


def load_limits(workload: str) -> dict:
    with open(os.path.join(HERE, "limits", workload + ".json")) as f:
        return json.load(f)["limits"]


def judge(readings: dict, limits: dict) -> tuple[bool, dict]:
    """Each reading beside its limit; correct when every reading is a
    finite number within it."""
    checks, ok = {}, True
    for name, value in readings.items():
        limit = limits[name]
        good = value == value and value <= limit   # NaN fails
        ok = ok and good
        checks[name] = {"value": value, "limit": limit}
    return ok, checks


def emit(result: dict, checks: dict, notes: dict | None = None):
    """Print the compared numbers as the last lines of stderr, then the
    result as the last line of stdout with ``checks`` as its last key."""
    for k, v in (notes or {}).items():
        print(f"# {k}: {v}", file=sys.stderr)
    for k, v in checks.items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    out = dict(result)
    out["checks"] = checks
    print(json.dumps(out), flush=True)
