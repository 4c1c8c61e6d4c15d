"""From a `jax.profiler` trace to device busy time, program time and gaps.

Reads the `.xplane.pb` with `jax.profiler.ProfileData` (nothing but
JAX). The window is the benchmark's own `bench.window` annotation on
the host plane. On each chip in use (planes `/device:TPU:<n>`):

* busy: the union of the intervals of the ops on the `XLA Ops` line,
  clipped to the window; `busy_s` is its mean over the chips;
* programs: the summed duration of the `XLA Modules` line's events per
  jitted function, named as `jax.jit` names them (`jit_<fn>(<id>)` ->
  `<fn>`), summed over the chips;
* ops: every op's self time (less the ops nested inside it), summed
  by name over the chips; device_ops: the `TOP` that took the most;
* idle_gaps: the longest stretches between busy intervals on the first
  chip, each named by the innermost host span around its midpoint: the
  benchmark's `bench.*` annotations, and the program's own spans when
  the caller maps them onto the trace's clock.
"""
from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
ANCHOR = "bench.anchor"
TOP = 10

Interval = Tuple[int, int]


def program_name(module: str) -> str:
    name = re.sub(r"\(\d+\)$", "", module)
    return name[4:] if name.startswith("jit_") else name


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _clip(iv: Interval, w: Interval) -> Optional[Interval]:
    a, b = max(iv[0], w[0]), min(iv[1], w[1])
    return (a, b) if b > a else None


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def op_name(hlo: str) -> str:
    """`%fusion.12 = bf16[...] fusion(...)` -> `fusion.12`."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def self_times(events) -> Dict[str, float]:
    """Seconds per op name, each op less the ops nested inside it (a
    `while` holds its body's ops on the same line)."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []          # [name, end, own seconds]

    def pop():
        name, _, own = stack.pop()
        out[name] += own

    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            pop()
        if stack:
            stack[-1][2] -= (b - a) * 1e-9
        stack.append([op_name(name), b, (b - a) * 1e-9])
    while stack:
        pop()
    return out


def _host_spans(planes) -> Dict[str, List[Interval]]:
    spans: Dict[str, List[Interval]] = defaultdict(list)
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, a, b in _events(line):
                    if name.startswith("bench."):
                        spans[name].append((a, b))
    return spans


def load(path):
    """A profile from an `.xplane.pb` file, gzipped or not."""
    import gzip

    from jax.profiler import ProfileData
    data = Path(path).read_bytes()
    if data[:2] == b"\x1f\x8b":
        data = gzip.decompress(data)
    return ProfileData.from_serialized_xspace(data)


def reduce(profile, chips: int,
           host_spans: Optional[List[Tuple[str, float, float]]] = None
           ) -> Dict:
    """`host_spans`: extra (name, start, end) spans in seconds of the
    caller's clock, which the caller read at the `bench.anchor`
    annotation as `host_spans[0]` = ("bench.anchor", t, t)."""
    planes = list(profile.planes)
    bench = _host_spans(planes)
    if not bench.get(WINDOW):
        raise ValueError("trace has no bench.window annotation")
    window = bench[WINDOW][0]
    named = [(n, iv) for n, ivs in bench.items() if n != WINDOW
             for iv in ivs]
    if host_spans:
        (_, t_anchor, _), rest = host_spans[0], host_spans[1:]
        anchor_ns = bench[ANCHOR][0][0]
        to_ns = lambda t: int(anchor_ns + (t - t_anchor) * 1e9)
        named += [(n, (to_ns(a), to_ns(b))) for n, a, b in rest]

    devices = sorted((int(DEVICE_PLANE.match(p.name).group(1)), p)
                     for p in planes if DEVICE_PLANE.match(p.name))
    if len(devices) < chips:
        raise ValueError(f"trace holds {len(devices)} TPU planes, "
                         f"the run used {chips}")
    busy_ns, programs, ops = [], defaultdict(float), defaultdict(float)
    first_busy: List[Interval] = []
    for i, (_, plane) in enumerate(devices[:chips]):
        lines = {line.name: line for line in plane.lines}
        inside = [(n, *iv) for n, a, b in _events(lines[OPS_LINE])
                  for iv in [_clip((a, b), window)] if iv]
        for name, s in self_times(inside).items():
            ops[name] += s
        merged = merge((a, b) for _, a, b in inside)
        busy_ns.append(sum(b - a for a, b in merged))
        if i == 0:
            first_busy = merged
        modules = lines.get(MODULES_LINE)
        for name, a, b in _events(modules) if modules else ():
            iv = _clip((a, b), window)
            if iv:
                programs[program_name(name)] += (iv[1] - iv[0]) * 1e-9

    gaps = []
    edges = [(window[0], window[0])] + first_busy + [(window[1],
                                                      window[1])]
    for (_, end), (start, _) in zip(edges, edges[1:]):
        if start > end:
            gaps.append((_doing(named, (end + start) // 2), (start - end)
                         * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return {
        "window_s": (window[1] - window[0]) * 1e-9,
        "busy_s": sum(busy_ns) / len(busy_ns) * 1e-9,
        "programs": dict(programs),
        "ops": dict(ops),
        "device_ops": [[n, s] for n, s in sorted(
            ops.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[n, s] for n, s in gaps[:TOP]],
    }


def _doing(named, t: int) -> str:
    inside = [(b - a, n) for n, (a, b) in named if a <= t < b]
    return min(inside)[1] if inside else "host.other"
