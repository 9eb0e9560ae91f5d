"""Reduce a JAX profiler trace (`.xplane.pb`) to the numbers the per-layer
metrics read.

  window     the host span `bench.window` the harness opens around the
             measured window; every other number is clipped to it
  busy       the union of the intervals in which an operation ran on a
             device (the device planes' "XLA Ops" line; "XLA Modules"
             where a plane has no op line), averaged over devices
  modules    device seconds per XLA module (program), by module name with
             its numeric suffix removed, e.g. "jit_tick"
  ops        device self seconds per operation (time not spent in ops
             nested inside it, such as a scan's body under its while
             loop), as "<module> <instruction> <result shape>"
  idle gaps  the window minus busy, each gap charged to the innermost
             `bench.*` host span that holds its midpoint ("host.other"
             where none does)

`jax.profiler.ProfileData` reads the file; nothing else is needed.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

import numpy as np

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
_SUFFIX = re.compile(r"(\(\d+\)|\.\d+)$")
_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")


def module_name(name: str) -> str:
    """XLA module name without its numeric suffix: "jit_tick(12)" and
    "jit_tick.3" both become "jit_tick"."""
    prev = None
    while prev != name:
        prev, name = name, _SUFFIX.sub("", name)
    return name


def union(starts: np.ndarray, ends: np.ndarray) -> Tuple[np.ndarray,
                                                          np.ndarray]:
    """Merge intervals into disjoint, sorted [start, end) segments."""
    if len(starts) == 0:
        return np.zeros(0), np.zeros(0)
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    seg_start = s[new]
    idx = np.nonzero(new)[0]
    seg_end = reach[np.r_[idx[1:] - 1, len(s) - 1]]
    return seg_start, seg_end


@dataclass
class Summary:
    window_s: float
    busy_s: float                       # mean over devices
    devices: int
    module_s: Dict[str, float] = field(default_factory=dict)
    op_s: Dict[str, float] = field(default_factory=dict)
    idle_s: Dict[str, float] = field(default_factory=dict)
    gaps: int = 0

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        def head(d):
            return [[k, v] for k, v in sorted(d.items(),
                                              key=lambda kv: -kv[1])[:top]]
        return {"device_ops": head(self.op_s), "idle_gaps": head(self.idle_s)}


def op_label(text: str) -> str:
    """"%fusion.3 f32[8,256,4608]" from an XLA op event's HLO text
    "%fusion.3 = f32[8,256,4608]{2,1,0} fusion(...), kind=...": the
    instruction's name and its (first) result shape."""
    name, _, rest = text.partition(" = ")
    shape = _SHAPE.search(rest)
    return f"{name} {shape.group(0)}" if shape else name


def self_times(starts, ends) -> np.ndarray:
    """Duration of each interval minus the intervals nested inside it
    (intervals on one line nest or are disjoint)."""
    order = np.lexsort((-ends, starts))
    own = ends - starts
    stack: List[int] = []
    for i in order:
        while stack and ends[stack[-1]] <= starts[i]:
            stack.pop()
        if stack:
            own[stack[-1]] -= ends[i] - starts[i]
        stack.append(i)
    return own


def reduce_profile(pd) -> Summary:
    """Reduce a loaded ProfileData."""
    spans: List[Tuple[str, float, float]] = []
    device_lines = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" in lines or "XLA Modules" in lines:
                device_lines.append(lines)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} host span")
    lo, hi = max(windows, key=lambda w: w[1] - w[0])
    if not device_lines:
        raise ValueError("trace has no device plane with XLA ops")
    busy_total = 0.0
    module_s: Dict[str, float] = defaultdict(float)
    op_s: Dict[str, float] = defaultdict(float)
    busy_segments = []
    for lines in device_lines:
        mods = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                 module_name(ev.name))
                for ev in (lines["XLA Modules"].events
                           if "XLA Modules" in lines else ())]
        mods.sort()
        for ms, me, name in mods:
            s, e = max(ms, lo), min(me, hi)
            if e > s:
                module_s[name] += (e - s) * 1e-9
        src = lines["XLA Ops" if "XLA Ops" in lines else "XLA Modules"]
        names, starts, ends = [], [], []
        for ev in src.events:
            s, e = max(ev.start_ns, lo), min(ev.start_ns + ev.duration_ns, hi)
            if e > s:
                names.append(ev.name)
                starts.append(s)
                ends.append(e)
        starts, ends = np.asarray(starts, float), np.asarray(ends, float)
        if "XLA Ops" in lines and len(starts):
            mod_start = np.asarray([m[0] for m in mods], float)
            owner = np.searchsorted(mod_start, starts, side="right") - 1
            own = self_times(starts, ends)
            for name, k, t in zip(names, owner, own):
                mod = mods[k][2] if k >= 0 else "?"
                op_s[f"{mod} {op_label(name)}"] += t * 1e-9
        seg = union(starts, ends)
        busy_total += float(np.sum(seg[1] - seg[0])) * 1e-9
        busy_segments.append(seg)
    # idle gaps of the first device, charged to host spans
    seg_s, seg_e = busy_segments[0]
    gap_s = np.r_[lo, seg_e]
    gap_e = np.r_[seg_s, hi]
    keep = gap_e > gap_s
    gap_s, gap_e = gap_s[keep], gap_e[keep]
    idle = charge_gaps(gap_s, gap_e, spans, lo, hi)
    return Summary(window_s=(hi - lo) * 1e-9,
                   busy_s=busy_total / len(device_lines),
                   devices=len(device_lines), module_s=dict(module_s),
                   op_s=dict(op_s), idle_s=dict(idle), gaps=len(gap_s))


def charge_gaps(gap_s, gap_e, spans, lo, hi) -> Dict[str, float]:
    """Seconds of idle gap charged to each host span name: a gap goes to
    the innermost (shortest) `bench.*` span holding its midpoint.  Spans of
    one name come from one thread and do not overlap."""
    mid = 0.5 * (gap_s + gap_e)
    best_len = np.full(len(mid), np.inf)
    label = np.full(len(mid), "host.other", dtype=object)
    by_name = defaultdict(list)
    for n, s, e in spans:
        if n != WINDOW_SPAN and e > lo and s < hi:
            by_name[n].append((s, e))
    for n, iv in by_name.items():
        iv = np.asarray(sorted(iv), float)
        i = np.searchsorted(iv[:, 0], mid, side="right") - 1
        ok = i >= 0
        j = np.where(ok, i, 0)
        inside = ok & (iv[j, 1] > mid)
        length = iv[j, 1] - iv[j, 0]
        better = inside & (length < best_len)
        best_len[better] = length[better]
        label[better] = n
    idle: Dict[str, float] = defaultdict(float)
    for n, s, e in zip(label, gap_s, gap_e):
        idle[n] += (e - s) * 1e-9
    return dict(idle)


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(files)}")
    return files[0]


def reduce_file(path: str) -> Summary:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))


def reduce_dir(trace_dir: str) -> Summary:
    return reduce_file(find_xplane(trace_dir))
