"""Profiler trace of the measured window, and its reduction to numbers.

``capture`` records the window with the JAX profiler (host annotations on,
Python function tracing off). ``load_events`` turns the ``.xplane.pb`` into
a small plain form: per device plane, its ``XLA Ops`` and ``XLA Modules``
events, and the benchmark's own host spans (names starting ``bench.``), each
as ``[name, start_ns, duration_ns]`` on the profiler's one clock. ``reduce``
works on that form only, so it is checked on a recorded trace
(``tests/data``) without a chip.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import tempfile
import time

DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)$")
WINDOW_SPAN = "bench.window"
TRACE_SECONDS = 2.0     # the traced window of a ``--trace 1`` run
TOP_N = 10


@contextlib.contextmanager
def capture():
    """Trace the device while the block runs. Yields a dict whose ``host``
    list the caller fills with its own spans, ``[name, start_ns,
    duration_ns]`` on the ``time.time_ns`` clock; once the block has ended
    the dict holds the plain form (``load_events``), with those spans and
    the block itself (``bench.window``) moved onto the trace's clock. The
    profiler's host tracer stays off: on a TPU v5e it halved the pace of the
    served loop, while device-only tracing left it unchanged."""
    import jax
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    rec = {"host": []}
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        t0 = time.time_ns()
        try:
            yield rec
        finally:
            t1 = time.time_ns()
            jax.profiler.stop_trace()
        ev = load_events(tmp)
        start = ev.pop("start_ns")
        spans = [[WINDOW_SPAN, t0, t1 - t0]] + rec["host"]
        ev["host"] = ([] if start is None else
                      [[name, a - start, d] for name, a, d in spans])
        rec.update(ev)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def hlo_name(name: str) -> str:
    """``fusion.50`` for an op event named by its whole HLO instruction
    (``%fusion.50 = bf16[8,4096]{...} fusion(...)``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def load_events(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out = {"devices": {}, "host": [], "start_ns": None}
    for plane in data.planes:
        if plane.name == "Task Environment":
            out["start_ns"] = dict(plane.stats).get("profile_start_time")
        m = DEVICE_PLANE.search(plane.name)
        if m:
            dev = out["devices"].setdefault(m.group(1),
                                            {"ops": [], "modules": []})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    dev[key].extend([hlo_name(e.name), e.start_ns,
                                     e.duration_ns] for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend([e.name, e.start_ns, e.duration_ns]
                                   for e in line.events
                                   if e.name.startswith("bench."))
    return out


def _clip(events, lo, hi):
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            yield name, a, b


def _union(intervals):
    """Merged, sorted ``(start, end)`` intervals."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _span_at(spans, t):
    """The shortest benchmark host span open at time ``t``."""
    best = None
    for name, start, dur in spans:
        if start <= t < start + dur and (best is None or dur < best[1]):
            best = (name, dur)
    return best[0] if best else "no bench span"


def reduce(events: dict, device_ids) -> dict | None:
    """Device numbers over the ``bench.window`` span, or ``None`` where the
    trace holds no window or no device events:

    - ``window_s``: the window's length;
    - ``busy_s``: per device, the union of op intervals inside the window,
      averaged over ``device_ids``;
    - ``module_s``: per device, the summed XLA module time inside the
      window, averaged likewise;
    - ``device_ops``: the ops that took most time, ``[name, seconds]``
      summed over devices and averaged;
    - ``idle_gaps``: the longest stretches with no op on a device, named by
      the benchmark host span open in their middle.
    """
    windows = [e for e in events["host"] if e[0] == WINDOW_SPAN]
    devs = [events["devices"].get(str(d)) for d in device_ids]
    if len(windows) != 1 or not all(d and d["ops"] for d in devs):
        return None
    _, w0, wdur = windows[0]
    w1 = w0 + wdur
    spans = [e for e in events["host"] if e[0] != WINDOW_SPAN]
    busy = module = 0.0
    op_time: dict[str, float] = {}
    gaps = []
    for dev in devs:
        ops = list(_clip(dev["ops"], w0, w1))
        for name, a, b in ops:
            op_time[name] = op_time.get(name, 0.0) + (b - a)
        merged = _union((a, b) for _, a, b in ops)
        busy += sum(b - a for a, b in merged)
        module += sum(b - a for _, a, b in _clip(dev["modules"], w0, w1))
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2) if edges[i + 1] > edges[i])
    n = len(devs)
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP_N]
    top_gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP_N]
    return {
        "window_s": wdur / 1e9,
        "busy_s": busy / n / 1e9,
        "module_s": module / n / 1e9,
        "device_ops": [[name, t / n / 1e9] for name, t in top_ops],
        "idle_gaps": [[_span_at(spans, (a + b) / 2), (b - a) / 1e9]
                      for a, b in top_gaps],
    }
