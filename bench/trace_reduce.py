"""Reduce a profiler trace (`.xplane.pb`) to the benchmark's numbers.

What it reads:

* device planes (`/device:TPU:<n>`): the line of XLA operations, each an
  interval on the device, and the line of XLA modules (whole jitted
  programs);
* the host plane: the benchmark's own annotations (`jax.profiler.
  TraceAnnotation`), one of which, `WINDOW`, bounds the traced window.

Operation names are the HLO instruction's name without its number
(`%fused_chunk_prefill_attention.9 = ...` is `fused_chunk_prefill_attention`;
a Pallas kernel carries the name of the function that calls it), and module
names drop the hash (`jit__lambda(123)` is `jit__lambda`). Control-flow
operations (`while`, `conditional`, `call`) contain other operations, so
they count towards busy time but not towards the time by name.

What it computes, over the window:

* busy seconds: the union of the operation intervals, averaged over the
  devices; the idle share is 1 − busy / window;
* device seconds by operation name and by module name, and how many times
  each module ran, over all devices and on each device;
* the device seconds of collective operations (`COLLECTIVES`), summed over
  devices, and the part of them during which no other operation runs on
  that device (exposed: nothing overlaps it);
* the longest idle gaps, each named by the host annotation that overlaps
  it most (what the host was doing while the device waited).

Only `jax.profiler.ProfileData` is used to read the file; the arithmetic
below is plain Python, so a test can feed it recorded intervals.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

CONTAINERS = ("while", "conditional", "call")

# Operations that move data between chips: a name containing one of these
# is a collective, its `-start`/`-done` halves and fusions included. The
# tp=4 serving path on a TPU v5e host runs `all-reduce` and `all-gather`,
# both synchronous: the "XLA Ops" line runs one operation at a time, so a
# synchronous collective is exposed whole.
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")

Interval = Tuple[float, float]          # seconds


def op_name(hlo: str) -> str:
    """`%fusion.12 = f32[...] ...` -> `fusion`."""
    head = hlo.split(" = ", 1)[0].strip().lstrip("%")
    base, _, num = head.rpartition(".")
    return base if base and num.isdigit() else head


def module_name(name: str) -> str:
    """`jit_train_step(1530...)` -> `jit_train_step`."""
    return name.split("(", 1)[0]


@dataclass
class Trace:
    window: Interval
    ops: Dict[str, List[Tuple[str, float, float]]]       # device -> events
    modules: Dict[str, List[Tuple[str, float, float]]]
    host: List[Tuple[str, float, float]] = field(default_factory=list)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str, host_names: Optional[Sequence[str]] = None) -> Trace:
    """Read one `.xplane.pb`. `host_names` limits the host annotations
    kept (the window marker is always kept)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[str, list] = {}
    modules: Dict[str, list] = {}
    host: list = []
    window = None
    keep = None if host_names is None else set(host_names) | {WINDOW}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                dst = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
                if dst is None:
                    continue
                norm = op_name if dst is ops else module_name
                dst.setdefault(plane.name, []).extend(
                    (norm(e.name), e.start_ns * 1e-9, e.end_ns * 1e-9)
                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns * 1e-9, e.end_ns * 1e-9)
                    elif keep is None or e.name in keep:
                        host.append((e.name, e.start_ns * 1e-9,
                                     e.end_ns * 1e-9))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} annotation")
    return Trace(window=window, ops=ops, modules=modules, host=host)


def clip(events, window: Interval):
    lo, hi = window
    for name, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield name, s, e


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping intervals, sorted."""
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    """Idle intervals of the window between merged busy intervals."""
    out, t = [], window[0]
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if window[1] > t:
        out.append((t, window[1]))
    return out


def _overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def label_gap(gap: Interval, host) -> str:
    """The host annotation that overlaps the gap most, or 'no span'."""
    best, name = 0.0, "no span"
    for n, s, e in host:
        ov = _overlap(gap, (s, e))
        if ov > best:
            best, name = ov, n
    return name


def is_collective(name: str) -> bool:
    return any(c in name for c in COLLECTIVES)


def overlap_s(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Seconds in both of two merged, sorted interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        total += _overlap(a[i], b[j])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def reduce(trace: Trace, top: int = 10) -> Dict:
    """busy_s (mean over devices), window_s, op_s / module_s by name
    (summed over devices), module_n (runs of each module, over devices),
    module_n_by_device (runs of each module on each device that ran one),
    collective_s and collective_exposed_s (summed over devices), and the
    `top` longest device operations and idle gaps of the first device."""
    w = trace.window
    window_s = w[1] - w[0]
    busy, op_s, mod_s = [], defaultdict(float), defaultdict(float)
    mod_n = defaultdict(int)
    by_dev: Dict[str, Dict[str, int]] = {}
    coll_s = exposed_s = 0.0
    first_gaps: List[Interval] = []
    for i, dev in enumerate(sorted(trace.ops)):
        evs = list(clip(trace.ops[dev], w))
        coll, other = [], []
        for name, s, e in evs:
            if name in CONTAINERS:
                continue
            op_s[name] += e - s
            (coll if is_collective(name) else other).append((s, e))
        coll_s += sum(e - s for s, e in coll)
        coll = union(coll)
        exposed_s += sum(e - s for s, e in coll) - overlap_s(coll,
                                                             union(other))
        merged = union([(s, e) for _, s, e in evs])
        busy.append(sum(e - s for s, e in merged))
        if i == 0:
            first_gaps = gaps(merged, w)
    for dev, evs in trace.modules.items():
        for name, s, e in clip(evs, w):
            mod_s[name] += e - s
            mod_n[name] += 1
            n = by_dev.setdefault(dev, {})
            n[name] = n.get(name, 0) + 1
    longest = sorted(first_gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "op_s": dict(op_s),
        "module_s": dict(mod_s),
        "module_n": dict(mod_n),
        "module_n_by_device": by_dev,
        "collective_s": coll_s,
        "collective_exposed_s": exposed_s,
        "device_ops": sorted(([n, t] for n, t in op_s.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[label_gap(g, trace.host), g[1] - g[0]]
                      for g in longest],
    }


def seconds_matching(by_name: Dict[str, float],
                     patterns: Sequence[str]) -> float:
    """Total seconds of the names that contain any of `patterns`."""
    return sum(t for n, t in by_name.items()
               if any(p in n for p in patterns))
