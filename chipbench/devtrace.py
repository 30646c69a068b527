"""Reduction of a JAX profiler trace (``*.xplane.pb``) to device metrics.

* busy seconds: the union of the intervals in which an operation ran on
  a device (the device plane's ``XLA Ops`` line), inside the traced
  window, averaged over the devices;
* window seconds: the length of the harness's ``traced_window`` span;
* top device operations by total time;
* the longest idle gaps, each labelled with the harness span (``submit``,
  ``engine.step``, ``harness.account``, ``wait_arrival``) that covers
  most of it on the host.
"""
from __future__ import annotations

import glob
import os

WINDOW_SPAN = "traced_window"
HOST_SPANS = ("submit", "engine.step", "harness.account", "wait_arrival")
OPS_LINE = "XLA Ops"


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(a0, a1, b0, b1):
    return max(0, min(a1, b1) - max(a0, b0))


def op_name(hlo: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def collect(planes) -> dict:
    """Pull what the reduction needs out of ``ProfileData.planes`` (or any
    objects with the same ``name``/``lines``/``events`` shape): host
    spans by name, and per device its op events (name, start, end), all
    in nanoseconds."""
    host = {name: [] for name in (WINDOW_SPAN,) + HOST_SPANS}
    devices = {}
    for plane in planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((op_name(ev.name), ev.start_ns,
                                ev.start_ns + ev.duration_ns)
                               for ev in line.events)
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in host:
                        host[ev.name].append((ev.start_ns,
                                              ev.start_ns + ev.duration_ns))
    return {"host": host, "devices": devices}


def reduce(col: dict, top: int = 10) -> dict:
    """-> {busy_s, window_s, device_ops, idle_gaps}, or {} when the trace
    holds no traced window or no device operation."""
    win = col["host"][WINDOW_SPAN]
    devs = {k: v for k, v in col["devices"].items() if v}
    if not win or not devs:
        return {}
    w0, w1 = min(s for s, _ in win), max(e for _, e in win)
    busy_total, per_op, gaps = 0, {}, []
    for ops in devs.values():
        clipped = [(max(s, w0), min(e, w1)) for _, s, e in ops if e > w0 and s < w1]
        merged = _merge(clipped)
        busy_total += sum(e - s for s, e in merged)
        for name, s, e in ops:
            d = _overlap(s, e, w0, w1)
            if d:
                per_op[name] = per_op.get(name, 0) + d
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                gaps.append((g0, g1))
    spans = [(name, s, e) for name in HOST_SPANS for s, e in col["host"][name]]
    labelled = []
    for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, label = 0, "untracked"
        for name, s, e in spans:
            o = _overlap(g0, g1, s, e)
            if o > best:
                best, label = o, name
        labelled.append([label, (g1 - g0) / 1e9])
    ops_sorted = sorted(per_op.items(), key=lambda x: -x[1])[:top]
    return {"busy_s": busy_total / len(devs) / 1e9,
            "window_s": (w1 - w0) / 1e9,
            "device_ops": [[n, d / 1e9] for n, d in ops_sorted],
            "idle_gaps": labelled}


def reduce_dir(log_dir: str, top: int = 10) -> dict:
    from jax.profiler import ProfileData

    return reduce(collect(ProfileData.from_file(find_xplane(log_dir)).planes),
                  top)
