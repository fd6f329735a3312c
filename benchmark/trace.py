"""Reduce a JAX profiler trace to device busy time, top ops and idle gaps.

Read with ``jax.profiler.ProfileData``.  On a TPU each device is a plane
named ``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per
operation the device ran, with start and duration in nanoseconds on the
trace's own clock, which the host planes share.  A device is busy where
any of its ops runs: the union of those intervals.  Idle is the rest of
the traced slice.  The harness passes the slice as ``[t0_ns, t1_ns]`` on
the trace's clock (it marks both ends with a ``TraceAnnotation``), so
that idle time at the slice's ends counts too.
"""

from __future__ import annotations

import re
from pathlib import Path

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
#: the host annotation that marks the traced slice's ends
MARK = "benchmark.slice"


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge [start, end] intervals into disjoint ones, in order."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def self_seconds(events: list[tuple[float, float, str]]) -> dict:
    """Seconds per op name, each event less the events nested in it (a
    ``while`` op spans the ops of its body on the same line)."""
    out: dict = {}
    stack: list[list] = []  # [end, name, self_ns]
    for a, b, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= a:
            end, n, own = stack.pop()
            out[n] = out.get(n, 0.0) + own / 1e9
        if stack:
            stack[-1][2] -= b - a
        stack.append([b, name, b - a])
    for _, n, own in stack:
        out[n] = out.get(n, 0.0) + own / 1e9
    return out


def op_name(full: str) -> str:
    """``%fusion.7 = bf16[...] fusion(...)`` -> ``fusion.7``."""
    return full.split(" = ", 1)[0].lstrip("%").strip()


def find_xplane(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise ValueError(f"want one .xplane.pb under {log_dir}, found {found}")
    return found[0]


def load(path: Path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(path))


def marks(pd) -> list[float]:
    """Start times (ns, trace clock) of the slice-marking annotations."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend(e.start_ns for e in line.events if e.name == MARK)
    return sorted(out)


def reduce(pd, devices: list[int] | None = None,
           slice_ns: tuple[float, float] | None = None) -> dict:
    """Per device: busy seconds, idle gaps and op seconds inside the
    slice.  ``slice_ns`` defaults to the span of the two slice marks.
    Returns ``{"slice_s", "t0_ns", "devices": {id: {"busy_s", "gaps",
    "ops"}}}`` with gaps as ``(start_ns, end_ns)`` on the trace clock
    and ops as ``{name: seconds}``."""
    if slice_ns is None:
        m = marks(pd)
        if len(m) < 2:
            raise ValueError(f"want two {MARK!r} marks, found {len(m)}")
        slice_ns = (m[0], m[-1])
    t0, t1 = slice_ns
    out: dict = {"slice_s": (t1 - t0) / 1e9, "t0_ns": t0, "devices": {}}
    for plane in pd.planes:
        mt = DEVICE_PLANE.match(plane.name)
        if not mt:
            continue
        dev = int(mt.group(1))
        if devices is not None and dev not in devices:
            continue
        events = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                a = max(t0, e.start_ns)
                b = min(t1, e.start_ns + e.duration_ns)
                if b > a:
                    events.append((a, b, op_name(e.name)))
        busy = union([(a, b) for a, b, _ in events])
        gaps, prev = [], t0
        for a, b in busy:
            if a > prev:
                gaps.append((prev, a))
            prev = b
        if t1 > prev:
            gaps.append((prev, t1))
        out["devices"][dev] = {
            "busy_s": sum(b - a for a, b in busy) / 1e9,
            "busy": busy,
            "gaps": gaps,
            "ops": self_seconds(events),
        }
    if devices is not None and sorted(out["devices"]) != sorted(devices):
        raise ValueError(f"trace holds devices {sorted(out['devices'])}, "
                         f"want {sorted(devices)}")
    return out


def busy_s(red: dict) -> float:
    """Busy seconds averaged over the traced devices."""
    devs = red["devices"].values()
    return sum(d["busy_s"] for d in devs) / len(devs)


def idle_share(red: dict) -> float:
    """Idle share of the slice in %, averaged over the devices."""
    return 100.0 * (1.0 - busy_s(red) / red["slice_s"])


def top_ops(red: dict, n: int = 10) -> list[list]:
    """The n ops with the most device seconds, averaged over devices."""
    tot: dict = {}
    k = len(red["devices"])
    for d in red["devices"].values():
        for name, s in d["ops"].items():
            tot[name] = tot.get(name, 0.0) + s / k
    return [[name, s] for name, s in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def top_gaps(red: dict, name_at, n: int = 10) -> list[list]:
    """The n longest gaps in which no traced device ran an op, each
    named by ``name_at(midpoint_ns)``: what the host was doing then."""
    busy = union([iv for d in red["devices"].values() for iv in d["busy"]])
    t0 = red["t0_ns"]
    t1 = t0 + red["slice_s"] * 1e9
    gaps, prev = [], t0
    for a, b in busy + [(t1, t1)]:
        if a > prev:
            gaps.append((a - prev, (a + prev) / 2))
        prev = max(prev, b)
    gaps.sort(key=lambda g: -g[0])
    return [[name_at(mid), dur / 1e9] for dur, mid in gaps[:n]]
