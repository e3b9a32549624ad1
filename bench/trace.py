"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

* Device planes are the planes named ``/device:TPU:<n>``; their ``XLA Ops``
  line holds one event per operation that ran, named by its HLO text, and
  their ``XLA Modules`` line one event per program execution.  A program
  is known by the host span that was waiting for it (its ``span``), which
  also holds for a program that runs under no name of its own.
* Host spans are the ``bench.*`` annotations the harness opens around the
  calls into each layer; ``bench.window`` spans the traced window, and
  zero-length ``bench.mark.*`` events carry counts as stats.  The
  program's own ``serving.*`` spans are read by ``bench/spans.py`` from
  the same profile.

Busy time is the union of the operation intervals inside the window,
averaged over the device planes.  An idle gap is named by the innermost
host span open at its midpoint.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
MARK_PREFIX = "bench.mark."
SPAN_PREFIX = "bench."
OUTSIDE = "outside any span"


# ops that only wrap others (the layer loop): left out of the breakdown
WRAPPERS = re.compile(r"^%(while|conditional|call)[.\d]* ")


@dataclass
class Op:
    name: str               # HLO text of the op, or the program's name
    start: float            # ns
    dur: float              # ns
    span: str = OUTSIDE     # host span waiting for it (programs only)


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float           # averaged over device planes
    n_devices: int
    ops: list = field(default_factory=list)        # [Op], device 0
    modules: list = field(default_factory=list)    # [Op], device 0
    idle_by_span: dict = field(default_factory=dict)
    marks: dict = field(default_factory=dict)      # name -> {stat: sum}

    def op_seconds(self, pattern: str) -> float:
        """Device seconds of the ops whose HLO text matches ``pattern``."""
        rx = re.compile(pattern)
        return sum(o.dur for o in self.ops if rx.search(o.name)) / 1e9

    def module_stats(self, span: str) -> tuple[float, int]:
        """(device seconds, executions) of the programs the host waited
        for inside ``span``."""
        hit = [m for m in self.modules if m.span == span]
        return sum(m.dur for m in hit) / 1e9, len(hit)

    def top_ops(self, n: int = 10) -> list:
        acc = defaultdict(float)
        for o in self.ops:
            if not WRAPPERS.match(o.name):
                acc[_op_key(o.name)] += o.dur / 1e9
        return sorted(([k, v] for k, v in acc.items()),
                      key=lambda kv: -kv[1])[:n]

    def top_idle(self, n: int = 10) -> list:
        return sorted(([k, v] for k, v in self.idle_by_span.items()),
                      key=lambda kv: -kv[1])[:n]


def _op_key(hlo: str) -> str:
    """An op's instruction name without its number, and its result type
    without layouts: ``%fusion.12 = bf16[32,32,151936]{...} ...`` →
    ``fusion bf16[32,32,151936]``; the Pallas kernel is ``tpu_custom_call``."""
    if 'custom_call_target="tpu_custom_call"' in hlo:
        return "tpu_custom_call " + hlo.split(" = ", 1)[-1].split(" ", 1)[0] \
            .split("{")[0][:60]
    m = re.match(r"^%([A-Za-z_\-]+?)(?:[.\d]+)? = (\(?[a-z0-9]+\[[\d,]*\])",
                 hlo)
    return f"{m.group(1)} {m.group(2)}" if m else hlo[:60]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def load_profile(trace):
    """``trace`` as a ``jax.profiler.ProfileData``: a path to an
    ``.xplane.pb`` is read, a loaded profile is returned as it is."""
    if hasattr(trace, "planes"):
        return trace
    import jax
    return jax.profiler.ProfileData.from_file(str(trace))


def reduce_trace(trace) -> TraceSummary:
    """Reduce ``trace`` (a path to an ``.xplane.pb``, or its loaded
    ``jax.profiler.ProfileData``)."""
    pd = load_profile(trace)
    spans, window = [], None
    devices = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                dst = ops if line.name == OPS_LINE else \
                    mods if line.name == MODULES_LINE else None
                if dst is None:
                    continue
                for ev in line.events:
                    dst.append(Op(ev.name, ev.start_ns, ev.duration_ns))
            devices.append((plane.name, ops, mods))
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith(SPAN_PREFIX):
                    continue
                if ev.name == WINDOW_SPAN:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif not ev.name.startswith(MARK_PREFIX):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    if window is None:
        raise ValueError(f"{trace}: no {WINDOW_SPAN} span")
    if not devices:
        raise ValueError(f"{trace}: no device plane matching "
                         f"{DEVICE_PLANE.pattern}")
    lo, hi = window
    devices.sort()
    spans.sort()
    busy, idle = [], defaultdict(float)
    for d, (_, ops, _) in enumerate(devices):
        merged = _union((max(o.start, lo), min(o.start + o.dur, hi))
                        for o in ops if o.start + o.dur > lo and o.start < hi)
        busy.append(sum(e - s for s, e in merged))
        if d:
            continue
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                sp = _innermost(spans, (s + e) / 2)
                idle[sp[2] if sp else OUTSIDE] += (e - s) / 1e9
    _, ops0, mods0 = devices[0]
    inside = lambda o: lo <= o.start and o.start + o.dur <= hi
    # the program a span waited for is the longest one run inside it; the
    # others are the small conversions of its arguments
    longest = {}
    for m in mods0:
        sp = _innermost(spans, m.start + m.dur / 2)
        if sp is not None and (sp not in longest or m.dur > longest[sp].dur):
            longest[sp] = m
    for sp, m in longest.items():
        m.span = sp[2]
    return TraceSummary(
        window_s=(hi - lo) / 1e9, busy_s=sum(busy) / len(busy) / 1e9,
        n_devices=len(devices), ops=[o for o in ops0 if inside(o)],
        modules=[m for m in mods0 if inside(m)], idle_by_span=dict(idle),
        marks=_marks_in_window(pd, lo, hi))


def _marks_in_window(pd, lo, hi) -> dict:
    """Stats of the ``bench.mark.*`` events inside the window, summed per
    mark name, with their ``count``."""
    out = defaultdict(lambda: defaultdict(float))
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(MARK_PREFIX) and lo <= ev.start_ns <= hi:
                    for k, v in ev.stats:
                        out[ev.name][k] += float(v)
                    out[ev.name]["count"] += 1
    return {k: dict(v) for k, v in out.items()}


def _innermost(spans, t):
    """The innermost span (start, end, name) covering ``t``, or None: of
    properly nested spans on one thread, the covering one that started
    last.  ``spans`` is sorted by start."""
    i = bisect.bisect_right(spans, (t, float("inf"), "")) - 1
    while i >= 0:
        if spans[i][1] >= t:
            return spans[i]
        i -= 1
    return None
