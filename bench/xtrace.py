"""From a profiler trace (``.xplane.pb``) to device busy time, per-op
device time, exposed collective time, and idle gaps attributed to the
benchmark's own host spans.

``load`` reads the trace with ``jax.profiler.ProfileData`` into plain
lists; everything else works on those lists, so that a reduction can be
checked on a small recorded trace.  Device planes are the planes named
``/device:TPU:<n>``.  On each, the line ``XLA Ops`` holds one event per
HLO operation run, named by its HLO line (``%fusion.12 = ...``, or for a
Pallas kernel ``%cws_encode_pallas.1 = ... custom-call(...)``), and
``XLA Modules`` one event per executable run (``jit_<function>(<id>)``).
Host spans are the events named ``bench.*`` on any host line.  All times
are in seconds.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_SPAN_PREFIX = "bench."
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all")


@dataclasses.dataclass
class Device:
    name: str
    ops: list        # (instruction name, start_s, end_s, HLO line)
    modules: list    # (name, start_s, end_s)


@dataclasses.dataclass
class Trace:
    devices: list    # of Device, sorted by name
    spans: list      # host spans (name, start_s, end_s)


def find_xplane(root: str) -> str:
    found = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return found[-1]


HLO_NAME = re.compile(r"^%?([\w.\-]+) = ")


def op_name(text: str) -> str:
    """An op event's name as the trace prints it is its whole HLO line
    (``%cws_encode_pallas.1 = s32[512,1024] custom-call(...)``); the
    instruction's own name is what stays stable."""
    m = HLO_NAME.match(text)
    return m.group(1) if m else text


def load(path: str) -> Trace:
    """The trace at ``path`` (``.xplane.pb``, or gzipped ``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(op_name(e.name), e.start_ns * 1e-9,
                            e.end_ns * 1e-9, e.name) for e in line.events]
                elif line.name == MODULES_LINE:
                    modules = [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                               for e in line.events]
            devices.append(Device(plane.name, ops, modules))
        else:
            for line in plane.lines:
                spans.extend((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                             for e in line.events
                             if e.name.startswith(HOST_SPAN_PREFIX))
    devices.sort(key=lambda d: d.name)
    return Trace(devices, sorted(spans, key=lambda s: s[1]))


# -- interval arithmetic --------------------------------------------------


def union(intervals) -> list:
    """Sorted, merged (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def subtract(a, b) -> float:
    """Length of the union of ``a`` not covered by the union of ``b``."""
    a, b = union(a), union(b)
    left, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                left += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            left += e - cur
    return left


# -- reductions -------------------------------------------------------------


def window(trace: Trace, name: str = "bench.window"):
    """(start, end) of the benchmark's window span."""
    for n, s, e in trace.spans:
        if n == name:
            return s, e
    raise ValueError(f"trace holds no {name!r} host span")


def busy_s(dev: Device, lo: float, hi: float) -> float:
    return total(clip(union((s, e) for _, s, e, _ in dev.ops), lo, hi))


def mean_busy_s(trace: Trace, lo: float, hi: float) -> float:
    """Busy seconds in [lo, hi], averaged over the trace's devices."""
    return sum(busy_s(d, lo, hi) for d in trace.devices) / len(trace.devices)


def idle_percent(trace: Trace, lo: float, hi: float) -> float:
    """Share of [lo, hi] in which no operation ran, averaged over devices."""
    return 100.0 * (1.0 - mean_busy_s(trace, lo, hi) / (hi - lo))


def op_time(dev: Device, match, lo: float, hi: float) -> float:
    """Device seconds of the ops for which ``match(name, hlo)`` holds."""
    return total(clip([(s, e) for n, s, e, h in dev.ops if match(n, h)],
                      lo, hi))


def exposed_collective_s(dev: Device, lo: float, hi: float) -> float:
    """Collective op time during which no other op runs on the device."""
    coll = clip([(s, e) for n, s, e, _ in dev.ops if COLLECTIVE.search(n)],
                lo, hi)
    other = clip([(s, e) for n, s, e, _ in dev.ops
                  if not COLLECTIVE.search(n)], lo, hi)
    return subtract(coll, other)


def top_ops(trace: Trace, lo: float, hi: float, n: int = 10) -> list:
    """[[op name with its numbered suffix dropped, seconds averaged over
    devices], ...] for the ``n`` that took most device time."""
    acc = {}
    for dev in trace.devices:
        for name, s, e, _ in dev.ops:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                key = re.sub(r"\.\d+$", "", name)
                acc[key] = acc.get(key, 0.0) + (e - s)
    nd = max(len(trace.devices), 1)
    return [[k, v / nd] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, lo: float, hi: float, n: int = 10) -> list:
    """Device-idle seconds inside the window, summed by the innermost
    benchmark host span that covers each idle instant (``host.other``
    where none does); the ``n`` largest, averaged over devices."""
    spans = [(name, s, e) for name, s, e in trace.spans
             if name != "bench.window"]
    acc = {}
    for dev in trace.devices:
        busy = clip(union((s, e) for _, s, e, _ in dev.ops), lo, hi)
        gaps, cur = [], lo
        for s, e in busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < hi:
            gaps.append((cur, hi))
        for gs, ge in gaps:
            covered = []
            for name, s, e in spans:
                part = clip([(s, e)], gs, ge)
                if part:
                    covered.append((e - s, name, part[0]))
            # innermost first: the shortest span wins each instant
            covered.sort()
            claimed = []
            for _, name, (s, e) in covered:
                t = subtract([(s, e)], claimed)
                if t > 0:
                    acc[name] = acc.get(name, 0.0) + t
                    claimed.append((s, e))
            rest = (ge - gs) - total(union(claimed))
            if rest > 0:
                acc["host.other"] = acc.get("host.other", 0.0) + rest
    nd = max(len(trace.devices), 1)
    return [[k, v / nd] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]
