"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time.

* Busy time is the union of the intervals in which an operation ran on a
  device, averaged over the device planes; idle share is 1 - busy/window.
* Program device time: the ``XLA Modules`` events of a program, found by
  the program's name (``jit_<function>``), summed.
* ``breakdown``: the device operations that took most time, and the idle
  gaps grouped by what the host was doing, read from the innermost host
  span over each gap.

The window is bounded by the benchmark's own host spans (names starting
``bench.``): from the start of the first to the end of the last.  Times
are seconds; the trace's own are nanoseconds.
"""

from __future__ import annotations

import glob
import os
from bisect import bisect_right
from collections import defaultdict
from dataclasses import dataclass

HOST_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Ev:
    name: str
    start: float          # ns
    end: float            # ns


@dataclass
class Trace:
    devices: dict         # plane name -> {"ops": [Ev], "modules": [Ev]}
    host: list            # [Ev] of every host line


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def read(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE not in lines and MODULES_LINE not in lines:
                continue
            rec = {"ops": [], "modules": []}
            for key, line in (("ops", OPS_LINE), ("modules", MODULES_LINE)):
                if line in lines:
                    rec[key] = [Ev(e.name, e.start_ns, e.start_ns + e.duration_ns)
                                for e in lines[line].events]
            devices[plane.name] = rec
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Ev(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events)
    return Trace(devices, host)


# -- interval arithmetic ---------------------------------------------------------


def merge(intervals) -> list[tuple[float, float]]:
    """Union of (start, end) intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of [lo, hi] that no merged interval covers."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def busy_ns(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in merge(clip(intervals, lo, hi)))


# -- reduction -------------------------------------------------------------------


def window_of(host: list, prefix: str = HOST_PREFIX) -> tuple[float, float]:
    own = [e for e in host if e.name.startswith(prefix)]
    if not own:
        raise ValueError(f"no host span named {prefix}* in the trace")
    return min(e.start for e in own), max(e.end for e in own)


def _op_label(ev: Ev, modules: list, starts: list) -> str:
    """``<program>/<hlo op>``: the op's short HLO name inside the program
    whose execution holds it."""
    op = ev.name.split(" = ")[0].strip()[:80]
    i = bisect_right(starts, ev.start) - 1
    if i >= 0 and modules[i].end >= ev.start:
        return f"{modules[i].name.split('(')[0]}/{op}"
    return op


#: idle gaps shorter than this are counted together, unlabelled
SHORT_GAP_NS = 100_000.0


def _innermost(evs: list, starts: list, t: float, lookback: int):
    best = None
    for e in evs[max(0, bisect_right(starts, t) - lookback):
                 bisect_right(starts, t)]:
        if e.end >= t and (best is None
                           or e.end - e.start < best.end - best.start):
            best = e
    return best


class _HostIndex:
    """Innermost host span over a point in time: the benchmark's own
    span, then any other host event inside it."""

    def __init__(self, host: list, prefix: str = HOST_PREFIX):
        self.prefix = prefix
        own = sorted((e for e in host if e.name.startswith(prefix)),
                     key=lambda e: e.start)
        other = sorted((e for e in host if not e.name.startswith(prefix)),
                       key=lambda e: e.start)
        self.own, self.own_starts = own, [e.start for e in own]
        self.other, self.other_starts = other, [e.start for e in other]

    def label(self, t: float) -> str:
        own = _innermost(self.own, self.own_starts, t, len(self.own))
        inner = _innermost(self.other, self.other_starts, t, 2000)
        parts = [own.name[len(self.prefix):] if own else "outside bench spans"]
        if inner is not None:
            parts.append(inner.name[:80])
        return " / ".join(parts)


def summarize(tr: Trace, window: tuple[float, float] | None = None,
              top: int = 10) -> dict:
    """busy_s (mean over devices), window_s, per-program device seconds
    and counts, and the breakdown lists."""
    if not tr.devices:
        raise ValueError("the trace holds no device plane")
    lo, hi = window if window is not None else window_of(tr.host)
    busy, programs = [], defaultdict(lambda: [0.0, 0])
    op_time = defaultdict(float)
    gap_time, gap_n = defaultdict(float), defaultdict(int)
    index = _HostIndex(tr.host)
    for rec in tr.devices.values():
        evs = rec["ops"] or rec["modules"]
        mods = sorted(rec["modules"], key=lambda e: e.start)
        mod_starts = [e.start for e in mods]
        ivs = [(e.start, e.end) for e in evs]
        busy.append(busy_ns(ivs, lo, hi))
        for e in rec["modules"]:
            if e.end > lo and e.start < hi:
                p = programs[e.name.split("(")[0]]
                p[0] += (min(e.end, hi) - max(e.start, lo)) / 1e9
                p[1] += 1
        for e in rec["ops"]:
            if e.end > lo and e.start < hi:
                op_time[_op_label(e, mods, mod_starts)] += (min(e.end, hi) - max(e.start, lo)) / 1e9
        for s, e in gaps(merge(clip(ivs, lo, hi)), lo, hi):
            lab = (index.label((s + e) / 2) if e - s >= SHORT_GAP_NS
                   else "gaps under 100 us")
            gap_time[lab] += (e - s) / 1e9
            gap_n[lab] += 1
    n = len(tr.devices)
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(gap_time.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": sum(busy) / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "programs": {k: {"seconds": v[0] / n, "count": v[1] // n}
                     for k, v in programs.items()},
        "device_ops": [[k, v / n] for k, v in ops],
        "idle_gaps": [[f"{k} (x{gap_n[k] // n})", v / n] for k, v in idle],
    }


def program_seconds(summary: dict, name: str) -> tuple[float, int] | None:
    """Device seconds and count of the program ``jit_<name>``, or None."""
    for key in (name, f"jit_{name}"):
        if key in summary["programs"]:
            p = summary["programs"][key]
            return p["seconds"], p["count"]
    return None
