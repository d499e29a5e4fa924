"""Reduction of a JAX profiler trace (xplane) to device metrics.

The benchmark's per-layer device numbers all come from here, so every PR
computes them the same way:

- device busy time: the union of the intervals in which an operation ran
  on a device ("XLA Ops" line of each `/device:*` plane), clipped to the
  measured window and averaged over the devices used;
- per-operation device time: the sum of the durations of the events whose
  name matches (a Pallas kernel carries the `name=` given to its
  `pallas_call`);
- idle time (gaps between device operations) summed by the innermost
  span (`engine.*` of the program, mirrored into the trace through
  `jax.profiler.TraceAnnotation`, or `bench.*` of the harness) that was
  open at each gap's midpoint.

The window is the host annotation `WINDOW_SPAN`, which the harness opens
around the measured window, so device and host share the trace's clock.
"""
from __future__ import annotations

import bisect
import glob
import os

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"


def load(log_dir: str):
    """The newest `.xplane.pb` under `log_dir` as a `ProfileData`."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return ProfileData.from_file(paths[-1])


def op_name(text: str) -> str:
    """An XLA op event's name: its HLO instruction name without the `%`
    and the rest of the instruction text (`%fusion.3 = s32[4] ...` ->
    `fusion.3`; a Pallas kernel's instruction carries its `name=`)."""
    if text.startswith("%"):
        return text[1:].split(" ", 1)[0]
    return text


def _events(line, name=lambda t: t):
    return [(name(e.name), float(e.start_ns),
             float(e.start_ns + e.duration_ns)) for e in line.events]


def split_planes(pd) -> tuple[dict[str, list], list]:
    """({TPU plane name: [(op, start_ns, end_ns)]}, host annotations
    [(name, start_ns, end_ns)] of the thread that opened the window).
    Host events from the Python tracer (names starting with `$`) are
    dropped; only annotations remain."""
    devices: dict[str, list] = {}
    lines: list[list] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU"):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(_events(line, op_name))
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            lines.extend([ev for ev in _events(line)
                          if not ev[0].startswith("$")]
                         for line in plane.lines)
    # the annotations of the thread that ran the window (the runtime's and
    # the compiler's own threads fill the host plane too)
    mine = [evs for evs in lines
            if any(n == WINDOW_SPAN for n, _, _ in evs)]
    host = [ev for evs in (mine or lines) for ev in evs]
    return devices, host


def window_of(host: list) -> tuple[float, float]:
    spans = [(s, e) for name, s, e in host if name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} annotation")
    return spans[-1]


def _clip(ops, t0: float, t1: float):
    return [(n, max(s, t0), min(e, t1)) for n, s, e in ops
            if e > t0 and s < t1]


def busy_intervals(ops) -> list[tuple[float, float]]:
    """Union of the [start, end) intervals of `ops`, sorted."""
    out: list[list[float]] = []
    for _n, s, e in sorted(ops, key=lambda o: o[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _is_span(name: str) -> bool:
    """A dotted span name (the program's `engine.decode`, the harness's
    `bench.sweep`, the runtime's `ArrayImpl.copy_to_host_async`), as
    against the runtime's per-function annotations (`PjitFunction(f)`)."""
    head, dot, _ = name.partition(".")
    return bool(dot) and head.isidentifier() and "(" not in name \
        and " " not in name


class _SpanIndex:
    """Innermost span open at a time, over properly nested spans."""

    def __init__(self, host):
        self.spans = sorted((s, e, n) for n, s, e in host
                            if n != WINDOW_SPAN and _is_span(n))
        self.starts = [s for s, _e, _n in self.spans]
        self.reach = []                  # latest end among spans[:i + 1]
        for _s, e, _n in self.spans:
            self.reach.append(max(e, self.reach[-1] if self.reach else e))

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.reach[i] > t:
            s, e, n = self.spans[i]
            if s <= t < e:
                return n
            i -= 1
        return "(no span)"


def reduce_trace(pd, *, top: int = 10) -> dict:
    """Busy and window seconds, device time by operation name, the `top`
    operations by device time and the `top` spans by idle time, over the
    `WINDOW_SPAN`."""
    devices, host = split_planes(pd)
    if not devices:
        raise ValueError("trace holds no device plane")
    t0, t1 = window_of(host)
    window_s = (t1 - t0) * 1e-9
    busy = []
    op_time: dict[str, float] = {}
    gaps: list[tuple[float, float]] = []
    for ops in devices.values():
        ops = _clip(ops, t0, t1)
        iv = busy_intervals(ops)
        busy.append(sum(e - s for s, e in iv) * 1e-9)
        for n, s, e in ops:
            op_time[n] = op_time.get(n, 0.0) + (e - s) * 1e-9
        edges = [t0] + [x for se in iv for x in se] + [t1]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    n_dev = len(devices)
    for k in op_time:
        op_time[k] /= n_dev
    # idle time summed by the innermost span open in each gap (eager
    # dispatch leaves many short gaps; their sum is what the host cost)
    index = _SpanIndex(host)
    idle: dict[str, float] = {}
    for s, e in gaps:
        name = index.at((s + e) / 2)
        idle[name] = idle.get(name, 0.0) + (e - s) * 1e-9 / n_dev
    idle_gaps = sorted(([n, t] for n, t in idle.items()),
                       key=lambda nt: -nt[1])[:top]
    device_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": sum(busy) / n_dev, "window_s": window_s,
            "devices": n_dev, "op_time": op_time,
            "device_ops": [[n, t] for n, t in device_ops],
            "idle_gaps": idle_gaps}


def op_seconds(reduced: dict, name: str) -> float:
    """Device seconds of the operations named `name` (exact name, or the
    name followed by a `.`/`:` suffix as XLA numbers repeated ops)."""
    total = 0.0
    for op, t in reduced["op_time"].items():
        if op == name or op.startswith(name + ".") or op.startswith(name + ":"):
            total += t
    return total


__all__ = ["WINDOW_SPAN", "load", "op_name", "split_planes", "window_of",
           "busy_intervals", "reduce_trace", "op_seconds"]
