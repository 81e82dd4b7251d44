"""Reduction of a profiler trace (``.xplane.pb``) to per-layer numbers.

Read with ``jax.profiler.ProfileData``.  Device planes are
``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds one event per
executed HLO operation (a ``while`` spans the operations of its body),
``Async XLA Ops`` one per asynchronous copy or collective from its start
to its end, and ``XLA Modules`` one per program run.  Busy time is the
``XLA Ops`` line's alone: an asynchronous transfer in flight is not
work of the chip's cores.  The host plane carries the harness's own
``TraceAnnotation`` spans (``bench.*``), which bound the traced window
and name what the host was doing during each idle gap.

Every number is a measure of a union of intervals, so nested or
overlapping events are never counted twice.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVES = ("collective-permute", "all-reduce", "all-gather",
               "reduce-scatter", "all-to-all")
HOST_PREFIX = "bench."
UNNAMED_GAP = "outside bench spans"
TOP = 10


# ------------------------------------------------------------ intervals
def union(iv):
    """Sorted, merged copy of ``[(start, end), ...]``."""
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def measure(iv) -> float:
    return sum(e - s for s, e in union(iv))


def clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def minus(a, b) -> float:
    """Measure of ``a`` not covered by ``b``."""
    a, b = union(a), union(b)
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def gaps(iv, lo, hi):
    """The idle intervals of ``[lo, hi]`` between the busy ``iv``."""
    out, cur = [], lo
    for s, e in union(clip(iv, lo, hi)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


# --------------------------------------------------------------- events
def is_collective(op: str) -> bool:
    return any(c in op for c in COLLECTIVES)


def module_name(event: str) -> str:
    """``jit_step(12)`` → ``jit_step``."""
    return event.split("(", 1)[0].strip()


def in_flight(ops):
    """Intervals during which a collective runs or is in flight: an
    async ``*-start`` opens one that the next ``*-done`` of the same
    kind closes; a synchronous collective is its own interval."""
    out, open_at = [], {}
    for name, s, e in sorted(ops, key=lambda o: o[1]):
        kind = next(c for c in COLLECTIVES if c in name)
        if "-start" in name:
            open_at.setdefault(kind, []).append(s)
        elif "-done" in name and open_at.get(kind):
            out.append((open_at[kind].pop(0), e))
        else:
            out.append((s, e))
    return out


def op_name(event: str) -> str:
    """``%fusion.79 = u32[...] fusion(...)`` → ``%fusion.79``: device op
    events carry the whole HLO instruction, operands included."""
    return event.split(" = ", 1)[0]


def _events(line, name=lambda n: n):
    return [(name(ev.name), ev.start_ns * 1e-9,
             (ev.start_ns + ev.duration_ns) * 1e-9) for ev in line.events]


def read_planes(pd):
    """(host spans, {device: {"ops" | "async" | "modules": [...]}})."""
    host, devices = [], {}
    for plane in pd.planes:
        m = DEVICE_PLANE.search(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            devices[int(m.group(1))] = {
                key: (_events(lines[line], op_name if key != "modules"
                              else module_name) if line in lines else [])
                for key, line in (("ops", OPS_LINE), ("async", ASYNC_LINE),
                                  ("modules", MODULES_LINE))}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [ev for ev in _events(line)
                         if ev[0].startswith(HOST_PREFIX)]
    return host, devices


def label_gap(host, s, e) -> str:
    """The innermost harness span around the middle of a gap."""
    mid = 0.5 * (s + e)
    around = [h for h in host if h[1] <= mid <= h[2]]
    if not around:
        return UNNAMED_GAP
    return min(around, key=lambda h: h[2] - h[1])[0]


def reduce(pd, *, n_devices: int, window: str = "bench.window",
           modules: dict | None = None) -> dict:
    """Per-layer numbers of one traced window.

    ``modules`` maps a role to the program-name prefix that plays it
    (``{"decode_step": "jit_step"}``); each role gets the durations of
    its program's runs on every device, in seconds.  Times are in
    seconds and averaged over the ``n_devices`` traced devices.
    """
    host, devices = read_planes(pd)
    spans = [h for h in host if h[0] == window]
    if len(spans) != 1:
        raise ValueError(f"expected one {window!r} span, found {len(spans)}")
    _, lo, hi = spans[0]
    host = [h for h in host if h[0] != window]
    devs = sorted(devices)[:n_devices]
    if len(devs) < n_devices or not any(devices[d]["ops"] for d in devs):
        raise ValueError(f"trace has device planes {sorted(devices)}, "
                         f"need {n_devices} with ops")
    busy = coll = exposed = noncoll = 0.0
    op_time: dict = {}
    roles = {role: [] for role in (modules or {})}
    for d in devs:
        ops = [o for o in devices[d]["ops"] if o[2] > lo and o[1] < hi]
        iv = clip([(s, e) for _, s, e in ops], lo, hi)
        busy += measure(iv)
        cops = [o for o in ops if is_collective(o[0])]
        other = clip([(s, e) for n, s, e in ops if not is_collective(n)],
                     lo, hi)
        wire = clip(in_flight(cops) + [(s, e) for n, s, e in
                                       devices[d]["async"]
                                       if is_collective(n)], lo, hi)
        coll += measure(wire)
        exposed += minus(wire, other)
        noncoll += measure(other)
        for n, s, e in ops:
            op_time[n] = op_time.get(n, 0.0) + min(e, hi) - max(s, lo)
        for name, s, e in devices[d]["modules"]:
            if e <= lo or s >= hi:
                continue
            for role, prefix in (modules or {}).items():
                if name == prefix:
                    roles[role].append(e - s)
    n = len(devs)
    idle = sorted(gaps([(s, e) for _, s, e in devices[devs[0]]["ops"]],
                       lo, hi), key=lambda g: g[0] - g[1])[:TOP]
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    return {"window_s": hi - lo, "busy_s": busy / n,
            "idle_share": 1.0 - busy / n / (hi - lo),
            "collective_s": coll / n, "exposed_collective_s": exposed / n,
            "noncollective_s": noncoll / n,
            "modules": roles, "n_devices": n,
            "top_ops": [[name, t / n] for name, t in top],
            "idle_gaps": [[label_gap(host, s, e), e - s] for s, e in idle]}


def xplane_files(where: str) -> list:
    return sorted(glob.glob(os.path.join(where, "**", "*.xplane.pb"),
                            recursive=True))


def reduce_dir(where: str, **kw) -> dict:
    """``reduce`` of the one trace the profiler wrote under ``where``."""
    from jax.profiler import ProfileData
    files = xplane_files(where)
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {where}, "
                         f"found {files}")
    return reduce(ProfileData.from_file(files[0]), **kw)
