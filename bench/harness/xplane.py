"""Reduce a JAX profiler trace (`.xplane.pb`) to device busy and idle time.

The benchmark marks its measured window on the host with a
`jax.profiler.TraceAnnotation` named `WINDOW`; everything is clipped to
that span.  For each device plane (`/device:TPU:<n>`), the operations on
its `XLA Ops` line (its `XLA Modules` line where it has none) give:

* busy seconds: the union of the operation intervals in the window;
* device time by operation name (summed over devices);
* idle gaps: the window less the busy union, each labelled by the
  shortest host event (any thread of `/host:CPU`) running at its
  midpoint, or "untraced host work" where none is.

Device and host events share the profiler's clock, so no offset is
applied.  The reduction reads nothing but the trace.
"""
from __future__ import annotations

from pathlib import Path

import numpy as np

WINDOW = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"    # read where a plane has no op line
HOST_PLANE = "/host:CPU"
UNTRACED = "untraced host work"
HOST_BINS = 2_000_000           # host-activity grid over the window


def find_xplane(log_dir: str | Path) -> Path:
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.duration_ns)


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge (n, 2) [start, end) intervals into disjoint sorted ones."""
    if not len(iv):
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


class _HostPaint:
    """What the host was doing at each instant of the window, on a grid of
    `HOST_BINS` bins: every bin holds the shortest host event (on any
    thread) that covers it, or nothing."""

    def __init__(self, w0: float, w1: float, host: list):
        self.w0 = w0
        self.step = max((w1 - w0) / HOST_BINS, 1.0)
        self.names = [UNTRACED]
        self.bins = np.zeros(HOST_BINS + 1, dtype=np.int32)
        ids: dict[str, int] = {}
        for s, e, name in sorted(host, key=lambda ev: ev[0] - ev[1]):
            b0 = max(0, int((s - w0) / self.step))
            b1 = min(HOST_BINS, int((e - w0) / self.step) + 1)
            if b1 <= b0:
                continue
            if name not in ids:
                ids[name] = len(self.names)
                self.names.append(name)
            self.bins[b0:b1] = ids[name]    # longest first: shorter win

    def label(self, t: float) -> str:
        b = min(HOST_BINS, max(0, int((t - self.w0) / self.step)))
        return self.names[self.bins[b]]


def reduce_trace(path: str | Path, top: int = 10) -> dict:
    """Busy/idle/op-time summary of the window in one trace file.

    Returns `busy_s` (mean over devices), `window_s`, `n_devices`,
    `busy_s_per_device`, `device_ops` and `idle_gaps` (top `top` each, as
    `[name, seconds]`, gaps summed per host label and averaged over
    devices).
    """
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    window = None
    host = []
    devices = []
    for plane in pd.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for name, s, d in _events(line):
                    if name == WINDOW:
                        window = (s, s + d)
                    else:
                        host.append((s, s + d, name))
        elif plane.name.startswith(DEVICE_PREFIX) and \
                plane.name[len(DEVICE_PREFIX):].isdigit():
            lines = {line.name: line for line in plane.lines}
            line = lines.get(OPS_LINE, lines.get(MODULES_LINE))
            if line is not None:
                devices.append((plane.name, list(_events(line))))
    if window is None:
        raise ValueError(f"trace {path} has no {WINDOW!r} span")
    if not devices:
        raise ValueError(f"trace {path} has no {DEVICE_PREFIX}<n> plane "
                         f"with an {OPS_LINE!r} or {MODULES_LINE!r} line")
    w0, w1 = window
    paint = _HostPaint(w0, w1, host)
    op_ns: dict[str, float] = {}
    gap_ns: dict[str, float] = {}
    busy = []
    for _, evs in devices:
        iv = []
        for name, s, d in evs:
            s, e = max(s, w0), min(s + d, w1)
            if e > s:
                iv.append((s, e))
                op_ns[name] = op_ns.get(name, 0.0) + (e - s)
        merged = _union(np.asarray(iv, dtype=np.float64))
        busy.append(float((merged[:, 1] - merged[:, 0]).sum()))
        edges = np.concatenate([[w0], merged.ravel(), [w1]]).reshape(-1, 2)
        gaps = edges[edges[:, 1] > edges[:, 0]]
        for g0, g1 in gaps:
            label = paint.label(0.5 * (g0 + g1))
            gap_ns[label] = gap_ns.get(label, 0.0) + (g1 - g0) / len(devices)

    def ranked(d: dict) -> list:
        return [[k, v * 1e-9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": float(np.mean(busy)) * 1e-9,
            "busy_s_per_device": [b * 1e-9 for b in busy],
            "window_s": (w1 - w0) * 1e-9,
            "n_devices": len(devices),
            "device_ops": ranked(op_ns),
            "idle_gaps": ranked(gap_ns)}
