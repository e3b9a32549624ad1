"""The serving path's own spans in a profiler trace.

The program opens ``serving.*`` spans (``repro.serving.telemetry.span``)
around each phase of a tick: the engine's admission, the scheduler's
choice, the backend's prefill and decode phases (plan, pack, launch,
wait, commit, ...).  They are written by the JAX profiler, so they share
the device trace's clock.  Every span carries its tick's step number as
the stat ``tick``; a launch span carries the dispatch's rows and tokens,
real and padded.

:func:`read_spans` gathers the spans that start inside the harness's
``bench.window`` (the whole trace where there is none, as in a trace
recorded on the CPU) and, where the trace has a device plane, splits the
device's idle time over the innermost serving span open during it: a gap
that outlasts one phase is cut at the phase's edges, not given whole to
the phase at its midpoint.  A trace of a program without these spans
gives an empty summary, whose readings are None.

A traced run of the harness hands this summary to every per-layer reader
as ``run.spans`` (None on an untraced run).  It is how a reader sees what
the program counts: a program counter is a numeric stat on a
``serving.*`` span, and a reader sums it over the window by the span's
name through :meth:`SpanSummary.by_name`, for example the rows of every
decode launch::

    def read(run):
        if run.spans is None:
            return None
        launch = run.spans.by_name().get("serving.backend.decode.launch")
        return launch["rows"] if launch else None

So a model whose layers count something (routed tokens, experts hit)
opens a span with that stat in the program and adds one reader file in
``bench/metrics/``; the harness is not edited.
"""

from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass, field

from bench.trace import (DEVICE_PLANE, OPS_LINE, OUTSIDE, WINDOW_SPAN,
                         _union, load_profile)

PREFIX = "serving."
TICK = "serving.engine.tick"
STEP = "serving.backend.step"
PREFILL_LAUNCH = "serving.backend.prefill.launch"
WAIT_SUFFIX = ".wait"
# spans that only enclose phases; every other serving span is a phase
ENCLOSING = (TICK, STEP)


@dataclass
class Span:
    name: str
    start: float            # ns
    end: float              # ns
    stats: dict


@dataclass
class SpanSummary:
    window_s: float
    spans: list = field(default_factory=list)     # [Span], by start
    # idle seconds of device 0 per innermost serving span (OUTSIDE: none);
    # None without a device plane
    idle_by_phase: dict | None = None
    idle_in_ticks_s: float | None = None   # idle inside tick spans

    def by_name(self) -> dict:
        """Per span name: ``count``, ``seconds`` and each stat summed."""
        out = defaultdict(lambda: defaultdict(float))
        for sp in self.spans:
            row = out[sp.name]
            row["count"] += 1
            row["seconds"] += (sp.end - sp.start) / 1e9
            for k, v in sp.stats.items():
                if k != "tick":
                    row[k] += v
        return {k: dict(v) for k, v in out.items()}

    def tick_host_ms(self) -> float | None:
        """Mean over ticks of the tick's duration less its ``*.wait``
        spans: host time per tick that overlaps no wait on the device."""
        ticks = {sp.stats.get("tick"): sp.end - sp.start
                 for sp in self.spans if sp.name == TICK}
        if not ticks:
            return None
        waits = defaultdict(float)
        for sp in self.spans:
            if sp.name.endswith(WAIT_SUFFIX):
                waits[sp.stats.get("tick")] += sp.end - sp.start
        host = [d - waits[t] for t, d in ticks.items()]
        return sum(host) / len(host) / 1e6

    def prefill_pad_pct(self) -> float | None:
        """Share of the prefill program's token slots that carry no prompt
        token, over the window's prefill launches."""
        launches = [sp.stats for sp in self.spans
                    if sp.name == PREFILL_LAUNCH]
        padded = sum(s["padded_tokens"] for s in launches)
        if not padded:
            return None
        return 100.0 * (1.0 - sum(s["tokens"] for s in launches) / padded)

    def leaf_idle_share(self) -> float | None:
        """Share of the device's idle time inside tick spans that falls
        under a phase span (any serving span but the enclosing ones)."""
        if not self.idle_in_ticks_s:
            return None
        leaf = sum(v for k, v in self.idle_by_phase.items()
                   if k != OUTSIDE and k not in ENCLOSING)
        return leaf / self.idle_in_ticks_s


def read_spans(trace) -> SpanSummary:
    """Summarize the serving spans of ``trace`` (a path to an
    ``.xplane.pb``, or its ``jax.profiler.ProfileData``)."""
    pd = load_profile(trace)
    spans, window, ops = [], None, None
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            if ops is None or plane.name < ops[0]:
                ops = (plane.name, [(ev.start_ns, ev.start_ns + ev.duration_ns)
                                    for line in plane.lines
                                    if line.name == OPS_LINE
                                    for ev in line.events])
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name.startswith(PREFIX):
                    spans.append(Span(ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns,
                                      dict(ev.stats)))
    if window is None:
        window = (min((s.start for s in spans), default=0.0),
                  max((s.end for s in spans), default=0.0))
    lo, hi = window
    spans = sorted((s for s in spans if lo <= s.start < hi),
                   key=lambda s: (s.start, -s.end))
    out = SpanSummary(window_s=(hi - lo) / 1e9, spans=spans)
    if ops is not None:
        busy = _union((max(s, lo), min(e, hi)) for s, e in ops[1]
                      if e > lo and s < hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        out.idle_by_phase = _split(gaps, _innermost_pieces(spans))
        ticks = [(s.start, s.end, TICK) for s in spans if s.name == TICK]
        out.idle_in_ticks_s = sum(
            v for k, v in _split(gaps, ticks).items() if k != OUTSIDE)
    return out


def _innermost_pieces(spans) -> list:
    """Disjoint ``(start, end, name)`` pieces naming, at each instant, the
    innermost span open.  ``spans`` are sorted by (start, -end) and nest
    (a span that outlasts its parent is cut at the parent's end)."""
    out, stack, t = [], [], None
    for sp in spans:
        while stack and stack[-1][0] <= sp.start:
            end, name = stack.pop()
            out.append((t, end, name))
            t = end
        if stack:
            out.append((t, sp.start, stack[-1][1]))
        end = min(sp.end, stack[-1][0]) if stack else sp.end
        stack.append((end, sp.name))
        t = sp.start
    while stack:
        end, name = stack.pop()
        out.append((t, end, name))
        t = end
    return [p for p in out if p[1] > p[0]]


def _split(gaps, pieces) -> dict:
    """Seconds of the ``gaps`` under each piece's name (``OUTSIDE`` for
    the parts under none); both lists sorted and disjoint."""
    out = defaultdict(float)
    j = 0
    for gs, ge in gaps:
        covered = 0.0
        while j < len(pieces) and pieces[j][1] <= gs:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < ge:
            s, e, name = pieces[k]
            d = min(e, ge) - max(s, gs)
            if d > 0:
                out[name] += d / 1e9
                covered += d
            k += 1
        if ge - gs > covered:
            out[OUTSIDE] += (ge - gs - covered) / 1e9
    return dict(out)
