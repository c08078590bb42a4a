"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

The window is the host span ``bench.window``. Device activity is the
``XLA Ops`` line of each ``/device:TPU:<n>`` plane, clipped to the window:
busy time is the union of its events' intervals, and a kernel's time is the
sum of its events' durations. Each op event is tagged with the program
(``XLA Modules`` line) it ran in. A gap in device activity is named by the
innermost ``bench.*`` host span that covers its middle.

The device's clock runs apart from the host's (on a v5e about 1.35 ms
behind, steady over a run). Program launches pair the two: the k-th
program on a device's ``XLA Modules`` line is the k-th host
``tpu::System::Execute``, counted from the start of the trace or from its
end, whichever pairing agrees with itself. Device times are moved by the
median difference; where neither pairing agrees within a tenth of a
millisecond, they are left as they are.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

WINDOW = "bench.window"
LAUNCH = "tpu::System::Execute"


@dataclass
class Op:
    name: str       # the HLO text of the op
    module: str     # the program it ran in, e.g. "jit_decode"
    start: float    # ns, trace clock
    dur: float      # ns


@dataclass
class Reduction:
    window: Tuple[float, float]                   # ns
    devices: int
    ops: List[Op] = field(default_factory=list)   # every device's ops
    busy_ns: float = 0.0                          # mean over devices
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9

    def kernel_s(self, match: Callable[[Op], bool]) -> float:
        return sum(o.dur for o in self.ops if match(o)) * 1e-9

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        tot: Dict[str, float] = {}
        for o in self.ops:
            key = f"{o.module}: {short(o.name)}"
            tot[key] = tot.get(key, 0.0) + o.dur * 1e-9
        return sorted(tot.items(), key=lambda kv: -kv[1])[:n]

    def top_gaps(self, n: int = 10) -> List[Tuple[str, float]]:
        return sorted(self.gaps, key=lambda g: -g[1])[:n]


def short(name: str) -> str:
    """``%fusion.12 = f32[..] fusion(..), kind=kLoop, calls=..`` ->
    ``fusion.12 fusion kLoop`` (instruction, opcode, fusion kind)."""
    m = re.match(r"%?([\w.\-]+) = .*?\s([\w\-]+)\(", name)
    if not m:
        return name[:80]
    kind = re.search(r"kind=(\w+)", name)
    target = re.search(r'custom_call_target="([\w\-]+)"', name)
    extra = (kind.group(1) if kind else "") or (target.group(1) if target
                                                 else "")
    return f"{m.group(1)} {m.group(2)} {extra}".strip()


def find(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def reduce(path: str, min_gap_ns: float = 1e5) -> Reduction:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans: List[Tuple[float, float, str]] = []
    launches: List[float] = []
    devs = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append((ev.start_ns, ev.start_ns
                                      + ev.duration_ns, ev.name))
                    elif ev.name == LAUNCH:
                        launches.append(ev.start_ns)
        elif re.match(r"/device:TPU:\d+$", plane.name):
            devs.append(plane)
    win = [s for s in spans if s[2] == WINDOW]
    if not win:
        raise ValueError(f"no {WINDOW} span in {path}")
    t0, t1 = win[0][0], win[0][1]
    red = Reduction(window=(t0, t1), devices=len(devs))
    inner = [s for s in spans if s[2] != WINDOW and s[1] > t0 and s[0] < t1]
    busy_total = 0.0
    launches.sort()
    for plane in devs:
        lines = {line.name: line for line in plane.lines}
        mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                       re.sub(r"\(.*$", "", ev.name))
                      for ev in lines["XLA Modules"].events) \
            if "XLA Modules" in lines else []
        shift = clock_shift([m[0] for m in mods], launches)
        mods = [(a + shift, b + shift, n) for a, b, n in mods]
        iv = []
        mi = 0
        for ev in sorted(lines["XLA Ops"].events if "XLA Ops" in lines
                         else [], key=lambda e: e.start_ns):
            a = ev.start_ns + shift
            b = a + ev.duration_ns
            if b <= t0 or a >= t1:
                continue
            while mi < len(mods) and mods[mi][1] < a:
                mi += 1
            mod = mods[mi][2] if mi < len(mods) and mods[mi][0] <= a else ""
            a, b = max(a, t0), min(b, t1)
            red.ops.append(Op(ev.name, mod, a, b - a))
            iv.append((a, b))
        merged = _union(iv)
        busy_total += sum(b - a for a, b in merged)
        edges = [t0] + [x for ab in merged for x in ab] + [t1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b - a >= min_gap_ns:
                red.gaps.append((_span_at(inner, (a + b) / 2), (b - a) * 1e-9))
    red.busy_ns = busy_total / max(len(devs), 1)
    return red


AGREE_NS = 1e5


def clock_shift(programs: List[float], launches: List[float]) -> float:
    """What to add to a device's times to put them on the host's clock."""
    n = min(len(programs), len(launches))
    for h, p in ((launches[:n], programs[:n]), (launches[-n:], programs[-n:])):
        d = sorted(a - b for a, b in zip(h, p))
        if d and d[-1 - n // 10] - d[n // 10] < AGREE_NS:
            return d[n // 2]
    return 0.0


def _span_at(spans, t) -> str:
    best: Optional[Tuple[float, float, str]] = None
    for s in spans:
        if s[0] <= t <= s[1] and (best is None or s[1] - s[0] < best[1] - best[0]):
            best = s
    return best[2] if best else "outside any bench span"
