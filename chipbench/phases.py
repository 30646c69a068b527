"""The engine's step phases, as a traced run recorded them.

The paged engine spans each phase of its step (``step.schedule``,
``step.prepare``, ``step.launch``, ``step.wait``, ``step.account``) and
each garbage collection (``gc``) with ``jax.profiler`` annotations, and
times the phases into the ``phase_ms`` of its ``step`` trace events
(``repro/obs/engine.py``).  Two readings come from them:

* ``host_ms``: one phase's mean wall time per engine step in the
  window, from the step events;
* ``step_host_share``: the share of the traced window in which no
  device op ran while the host was inside the engine's step: one of its
  phases or a collection (``PHASE_SPANS``), from the profiler trace.
  Each idle interval is split among the innermost host span covering
  each part of it, so idle time under the harness's spans (an arrival
  wait, the harness's own accounting, glue inside ``engine.step``) is
  not counted.

Idle under ``step.wait`` counts with the rest.  The profiler aligns its
device plane with the host's only to within about a millisecond (on a
TPU v5e the step's program can show up before its own ``step.launch``),
so a constant offset slides each idle gap across the boundary between
the ``wait`` that ends it on one side and the ``prepare``/``launch``
that end it on the other.  That moves idle from one phase to another
but leaves the sum over all of them as it was, for an offset shorter
than the phases at either end of the gap.  Idle per phase is therefore
no metric; ``host_ms`` names the phase, on the host's clock alone.

A program that records no phases gives None for both.
"""
from __future__ import annotations

import devtrace

PHASE_SPANS = ("step.schedule", "step.prepare", "step.launch", "step.wait",
               "step.account", "gc")


def host_ms(run, phase: str):
    """Mean of ``phase_ms[phase]`` over the engine's step events that
    start in the window, in ms."""
    vals = [ev["args"]["phase_ms"][phase] for ev in run.events or ()
            if ev.get("ph") == "X" and ev.get("name") == "step"
            and "phase_ms" in ev.get("args", {})
            and 0.0 <= ev["ts"] / 1e6 < run.window_s]
    return sum(vals) / len(vals) if vals else None


def collect(planes) -> dict:
    """``devtrace.collect`` plus the engine's phase spans, as (name,
    start, end) in nanoseconds, matched by bare name (an annotation's
    arguments may ride in the name as ``name#key=value#``)."""
    planes = list(planes)
    col = devtrace.collect(planes)
    col["phases"] = [
        (name, ev.start_ns, ev.start_ns + ev.duration_ns)
        for plane in planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
        for name in (ev.name.split("#", 1)[0],) if name in PHASE_SPANS]
    return col


def _idle(ops, w0, w1):
    """The window's intervals with no op of one device, sorted."""
    busy = devtrace._merge([(max(s, w0), min(e, w1)) for _, s, e in ops
                            if e > w0 and s < w1])
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def split_idle(idle, spans) -> dict:
    """Idle nanoseconds under each span name, each instant given to the
    innermost span covering it (the latest to start; of two that start
    together, the shorter).  ``idle``: sorted disjoint (start, end);
    ``spans``: (name, start, end)."""
    edges = sorted([(s, 1, i) for i, (_, s, _e) in enumerate(spans)] +
                   [(e, 0, i) for i, (_, _s, e) in enumerate(spans)])
    out, active, k, prev = {}, set(), 0, None
    for t, starts, i in edges:
        if active and t > prev:
            while k < len(idle) and idle[k][1] <= prev:
                k += 1
            got, j = 0, k
            while j < len(idle) and idle[j][0] < t:
                got += min(t, idle[j][1]) - max(prev, idle[j][0])
                j += 1
            if got:
                name = spans[max(active, key=lambda a: (spans[a][1],
                                                        -spans[a][2]))][0]
                out[name] = out.get(name, 0) + got
        prev = t
        if starts:
            active.add(i)
        else:
            active.discard(i)
    return out


def idle_by_span(col: dict):
    """-> (idle seconds under each span, averaged over the devices,
    window seconds), or None when the trace holds no traced window, no
    device op or no phase span."""
    win = col["host"][devtrace.WINDOW_SPAN]
    devs = [ops for ops in col["devices"].values() if ops]
    if not win or not devs or not col["phases"]:
        return None
    w0, w1 = min(s for s, _ in win), max(e for _, e in win)
    spans = [(n, s, e) for n in devtrace.HOST_SPANS
             for s, e in col["host"][n]] + col["phases"]
    total = {}
    for ops in devs:
        for name, ns in split_idle(_idle(ops, w0, w1), spans).items():
            total[name] = total.get(name, 0) + ns
    return ({n: ns / len(devs) / 1e9 for n, ns in total.items()},
            (w1 - w0) / 1e9)


def share(col: dict):
    """Idle under the step's phases over the traced window, in %, or
    None."""
    got = idle_by_span(col)
    if got is None:
        return None
    by_span, window_s = got
    return 100.0 * sum(by_span.get(n, 0.0) for n in PHASE_SPANS) / window_s


def step_host_share(run):
    """``share`` of the run's profiler trace (traced runs only)."""
    if not run.devtrace:
        return None
    import run_cell
    from jax.profiler import ProfileData

    path = devtrace.find_xplane(str(run_cell.TRACE_DIR))
    return share(collect(ProfileData.from_file(path).planes))
