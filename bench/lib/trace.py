"""Reduction of a JAX profiler trace to device busy time, kernel time
and idle gaps attributed to host spans.

Reads the `.xplane.pb` that `jax.profiler.trace` writes, with nothing
but `jax.profiler.ProfileData`.  Device planes are named
`/device:TPU:<n>`; their `XLA Ops` line holds one event per executed
HLO op (fusions, custom calls such as Pallas kernels, collectives).
Host spans are the benchmark's own `TraceAnnotation`s, named with the
prefix `bench.`, on the host plane's thread lines.  All times are in
nanoseconds on the host's clock.

The device's clock in the trace runs a little apart from the host's
(about a millisecond on a v5e).  `load` moves each device's events onto
the host clock by the least shift that puts no program execution (an
`XLA Modules` event) before the host call that launched it.
"""
from __future__ import annotations

import collections
import dataclasses
import pathlib
import shutil
import statistics

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
LAUNCH = "TpuLoadedExecutable::ExecuteLaunch"
SPAN_PREFIX = "bench."
# ops that contain other ops of the same line (a loop, a branch, a
# call): kept for busy time, left out of time by name
CONTAINERS = ("%while", "%conditional", "%call")


@dataclasses.dataclass
class Trace:
    ops: dict          # device plane name -> [(name, start_ns, end_ns)]
    spans: list        # [(name, start_ns, end_ns)] host spans
    shift_ns: dict     # device plane name -> ns added to its times


def xplane_file(log_dir) -> pathlib.Path:
    files = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load(path) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    ops, modules, spans, launches = {}, {}, [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend((op_name(e.name), int(e.start_ns),
                                int(e.end_ns)) for e in line.events)
                elif line.name == MODULES_LINE:
                    mods.extend(int(e.start_ns) for e in line.events)
            ops[plane.name] = sorted(evs, key=lambda e: e[1])
            modules[plane.name] = sorted(mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, int(e.start_ns),
                                      int(e.end_ns)))
                    elif e.name == LAUNCH:
                        launches.append(int(e.start_ns))
    shift = {}
    for name, mods in modules.items():
        d = clock_shift(sorted(launches) if len(ops) == 1 else [], mods)
        shift[name] = d
        ops[name] = [(n, s + d, e + d) for n, s, e in ops[name]]
    return Trace(ops=ops, spans=sorted(spans, key=lambda s: s[1]),
                 shift_ns=shift)


def op_name(text: str) -> str:
    """An op event's name: the HLO instruction's name, and for a custom
    call (a Pallas kernel) also its signature, the result and operand
    shapes that say which kernel it is and what it moved."""
    name, _, rest = text.partition(" = ")
    if "custom-call(" in rest:
        return f"{name} = {rest.split(', custom_call_target')[0]}"
    return name


def clock_shift(launches, modules) -> int:
    """ns to add to a device's times so that no execution starts before
    its launch, pairing the i-th launch with the i-th execution; 0 where
    they do not pair one to one (several devices, or a cut-off
    execution)."""
    if not launches or len(launches) != len(modules):
        return 0
    return max(h - d for h, d in zip(launches, modules))


def union(intervals) -> list:
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_ns(ops, lo=None, hi=None) -> int:
    """Length of the union of op intervals, within [lo, hi] if given."""
    iv = [(s, e) for _, s, e in ops]
    if lo is not None:
        iv = clip(iv, lo, hi)
    return sum(e - s for s, e in union(iv))


def time_by_name(ops, lo=None, hi=None) -> collections.Counter:
    """Summed device time per op name (ns), loops and other containers
    left out (their bodies' ops are counted)."""
    c = collections.Counter()
    for name, s, e in ops:
        if name.startswith(CONTAINERS):
            continue
        if lo is not None:
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
        c[name] += e - s
    return c


def gaps(ops, lo, hi) -> list:
    """Idle intervals of the device inside [lo, hi]."""
    out, cur = [], lo
    for s, e in union(clip([(s, e) for _, s, e in ops], lo, hi)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def span_at(spans, t) -> str:
    """Name of the innermost (latest-starting) host span covering t,
    or 'outside-spans'."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else "outside-spans"


def attributed_gaps(ops, spans, lo, hi, top: int = 10) -> list:
    """The `top` longest idle gaps, each named by the host span it fell
    in (at its midpoint): [[span name, seconds], ...]."""
    gs = sorted(gaps(ops, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return [[span_at(spans, (s + e) // 2), (e - s) * 1e-9] for s, e in gs]


def window(spans, name) -> tuple:
    """[start, end] of the named host span (the traced window)."""
    for n, s, e in spans:
        if n == name:
            return s, e
    raise KeyError(f"no host span {name!r} in the trace")


def read_window(log_dir, device_ids, span: str) -> tuple:
    """Load the trace written under `log_dir` (then delete it) and reduce
    the traced window, the host span `span`: (context for the per-layer
    readers, busy seconds averaged over the devices, window seconds,
    breakdown of the ten costliest device ops and the ten longest idle
    gaps)."""
    t = load(xplane_file(log_dir))
    shutil.rmtree(log_dir, ignore_errors=True)
    lo, hi = window(t.spans, span)
    planes = [t.ops.get(f"{DEVICE_PREFIX}{i}", []) for i in device_ids]
    busy = statistics.fmean(busy_ns(ops, lo, hi) for ops in planes) * 1e-9
    by_name = sum((time_by_name(ops, lo, hi) for ops in planes),
                  start=collections.Counter())
    breakdown = {
        "device_ops": [[k, v * 1e-9 / len(planes)]
                       for k, v in by_name.most_common(10)],
        "idle_gaps": attributed_gaps(planes[0], t.spans, lo, hi)}
    ctx = {"trace": t, "lo": lo, "hi": hi, "planes": planes,
           "busy_s": busy, "window_s": (hi - lo) * 1e-9}
    return ctx, busy, (hi - lo) * 1e-9, breakdown
