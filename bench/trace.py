"""Reduce a profiler trace (``.xplane.pb``) to what the readers need.

A TPU trace holds one plane per chip (``/device:TPU:<n>``) whose ``XLA
Ops`` line has one event per executed HLO op, named by the op's HLO text
(``%fusion.44 = f32[...] fusion(...), kind=kOutput, calls=...``), and
one ``XLA Modules`` line with one event per program run.  The host plane
(``/host:CPU``) has the Python thread's line, on which
``StepTraceAnnotation``/``TraceAnnotation`` spans appear by name (the
line that holds the ``step`` spans is taken as the Python thread's), and
the runtime's ``PJRT_LoadedExecutable_Execute`` calls.

The device's clock and the host's are not aligned to better than about a
millisecond, so device times are shifted by the smallest amount that
starts no program before the host call that launched it (programs and
launches paired in order).  The window is the host's first ``step`` span
start to its last ``step`` span end.
"""

from __future__ import annotations

import dataclasses
import re

STEP = "step"
_LAUNCH = "PJRT_LoadedExecutable_Execute"


def op_name(event_name: str) -> str:
    """``%fusion.44 = f32[...] fusion(...)`` -> ``fusion.44``."""
    return re.match(r"%?([^\s=]+)", event_name).group(1)


def union(intervals):
    """Sorted, merged ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy, lo, hi):
    """The parts of ``[lo, hi]`` that no interval of ``busy`` covers."""
    out, t = [], lo
    for s, e in union(clip(busy, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def innermost(spans, t):
    """Name of the shortest host span ``(start, end, name)`` covering t."""
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "none"


@dataclasses.dataclass
class Trace:
    """Times in seconds on the host's clock.

    ``ops[d]``: ``(start, end, op name)`` of device d's HLO ops;
    ``host``: ``(start, end, name)`` spans of the Python thread;
    ``window``: the traced steps, first start to last end."""
    ops: list
    host: list
    window: tuple

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s(self) -> float:
        """Time some op ran, in the window, averaged over the devices."""
        lo, hi = self.window
        per = [sum(e - s for s, e in union(clip([(a, b) for a, b, _ in ops],
                                                lo, hi)))
               for ops in self.ops]
        return sum(per) / len(per)

    def op_seconds(self) -> dict:
        """Op name -> its device time in the window, summed over runs and
        devices."""
        lo, hi = self.window
        out = {}
        for ops in self.ops:
            for s, e, name in ops:
                d = min(e, hi) - max(s, lo)
                if d > 0:
                    out[name] = out.get(name, 0.0) + d
        return out

    def idle_gaps(self):
        """``(what the host was doing, seconds)`` of every gap between
        device ops in the window, on each device, longest first."""
        lo, hi = self.window
        out = []
        for ops in self.ops:
            for s, e in gaps([(a, b) for a, b, _ in ops], lo, hi):
                out.append((innermost(self.host, (s + e) / 2), e - s))
        return sorted(out, key=lambda g: -g[1])


def _events(line):
    for ev in line.events:
        yield ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9, \
            ev.name


def from_profile(pd) -> Trace:
    """Reduce a ``jax.profiler.ProfileData``."""
    devices = sorted((p for p in pd.planes
                      if p.name.startswith("/device:TPU:")),
                     key=lambda p: p.name)
    py, launches, seen = [], [], {}
    for line in (ln for p in pd.planes if p.name.startswith("/host:")
                 for ln in p.lines):
        evs = list(_events(line))
        seen[line.name] = len(evs)
        if any(n == STEP for _, _, n in evs):
            py += evs
        launches += [s for s, _, n in evs if n == _LAUNCH]
    steps = [(s, e) for s, e, n in py if n == STEP]
    if not steps:
        raise ValueError(f"trace has no host '{STEP}' spans; host lines "
                         f"and their event counts: {seen}")
    ops = []
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        dev_ops = [(s, e, op_name(n)) for s, e, n in _events(lines["XLA Ops"])]
        modules = sorted(s for s, _, _ in _events(lines["XLA Modules"]))
        shift = 0.0
        if modules and len(modules) == len(launches):
            shift = max(0.0, max(h - d for h, d in
                                 zip(sorted(launches), modules)))
        ops.append([(s + shift, e + shift, n) for s, e, n in dev_ops])
    if not ops:
        raise ValueError("trace has no TPU device plane")
    return Trace(ops, py, (min(s for s, _ in steps), max(e for _, e in steps)))


def load(path) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(str(path)))
