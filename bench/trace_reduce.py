"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

- busy: the union of the intervals in which an operation ran on a device,
  inside the traced window, averaged over the devices used;
- kernel time: the summed device durations of the operations whose names
  contain one of a metric's patterns (kept as data in the metric's file);
- calls: how often each operation's full HLO text (its operand shapes
  with it) ran inside the window, for metrics that count work from shapes;
- idle gaps: the device's gaps inside the window, each labelled by the
  innermost benchmark host span (``bench.*``) that covers its midpoint.

Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane; host spans are events of the host planes.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
# Control-flow operations hold other operations of the same line; they count
# towards busy time but not as operations of their own.
CONTAINERS = ("while", "conditional", "call")


def op_name(text: str) -> str:
    """An operation's short name from its HLO text in the trace:
    ``%custom-call.79 = f32[...] custom-call(...), custom_call_target="X"``
    becomes ``custom-call.79 [X]``."""
    name = text.split(" = ", 1)[0].lstrip("%")
    marker = 'custom_call_target="'
    if marker in text:
        name += " [" + text.split(marker, 1)[1].split('"', 1)[0] + "]"
    return name


def _container(name: str) -> bool:
    return name.split(".", 1)[0] in CONTAINERS


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                       # mean over devices
    op_seconds: dict                    # op name -> summed device seconds
    op_calls: dict                      # op HLO text -> calls in the window
    gaps: list                          # [(label, seconds)], longest first
    devices: int

    def kernel_seconds(self, patterns) -> float:
        return sum(s for name, s in self.op_seconds.items()
                   if any(p in name for p in patterns))

    def calls(self, patterns) -> dict:
        """{HLO text: calls} of the operations whose names match."""
        return {text: n for text, n in self.op_calls.items()
                if any(p in op_name(text) for p in patterns)}

    def top_ops(self, n: int = 10) -> list:
        return sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:n]


def latest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce_events(device_ops, host_spans, t0_ns: float, t1_ns: float,
                  max_gaps: int = 10) -> Reduction:
    """``device_ops``: {device: [(HLO text or name, start_ns, dur_ns)]};
    ``host_spans``: [(name, start_ns, dur_ns)]; the window [t0, t1) in the
    same clock."""
    op_seconds = collections.Counter()
    op_calls = collections.Counter()
    names: dict = {}
    busy_total = 0.0
    gaps = []
    for dev, ops in device_ops.items():
        ivs = []
        for text, s, d in ops:
            clipped = _clip([(s, s + d)], t0_ns, t1_ns)
            if not clipped:
                continue
            (cs, ce), = clipped
            name = names.get(text)
            if name is None:
                name = names[text] = op_name(text)
            if not _container(name):
                op_seconds[name] += (ce - cs) * 1e-9
                op_calls[text] += 1
            ivs.append((cs, ce))
        merged = _union(ivs)
        busy_total += sum(e - s for s, e in merged) * 1e-9
        edges = [t0_ns] + [x for iv in merged for x in iv] + [t1_ns]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                gaps.append((gs, ge))
    spans = [(n, s, s + d) for n, s, d in host_spans
             if n.startswith(SPAN_PREFIX)]
    labelled = []
    for gs, ge in sorted(gaps, key=lambda g: g[0] - g[1])[:max_gaps]:
        mid = 0.5 * (gs + ge)
        cover = [(e - s, n) for n, s, e in spans if s <= mid < e]
        label = min(cover)[1] if cover else "no bench span"
        labelled.append((label, (ge - gs) * 1e-9))
    n_dev = max(len(device_ops), 1)
    return Reduction(
        window_s=(t1_ns - t0_ns) * 1e-9,
        busy_s=busy_total / n_dev,
        op_seconds=dict(op_seconds),
        op_calls=dict(op_calls),
        gaps=labelled,
        devices=len(device_ops),
    )


def read_xplane(path: str):
    """({device: [(op HLO text, start_ns, dur_ns)]},
    [(span, start_ns, dur_ns)]) from one trace file, both on the profiler's
    common clock.  Each distinct text is held once."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device_ops, host_spans, texts = {}, [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = [
                        (texts.setdefault(e.name, e.name), e.start_ns,
                         e.duration_ns)
                        for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_spans.extend(
                    (e.name, e.start_ns, e.duration_ns) for e in line.events
                    if e.name.startswith(SPAN_PREFIX)
                )
    return device_ops, host_spans


def reduce_trace(trace_dir: str, window_span: str = "bench.window",
                 max_gaps: int = 10) -> Reduction:
    """Reduce the newest trace under ``trace_dir`` over the window that the
    host span ``window_span`` marks."""
    device_ops, host_spans = read_xplane(latest_xplane(trace_dir))
    marks = [(s, s + d) for n, s, d in host_spans if n == window_span]
    if not marks:
        raise ValueError(f"no {window_span!r} span in the trace")
    t0, t1 = marks[0]
    return reduce_events(device_ops, host_spans, t0, t1, max_gaps)
