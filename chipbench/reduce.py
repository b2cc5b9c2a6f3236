"""Reduction of a profiler trace to device busy time, idle share,
collective exposure, the longest idle gaps and the costliest device ops.

A trace is reduced in two stages.  ``from_xspace`` reads the profiler's
``.xplane.pb`` into a ``Trace``: per device, the intervals of its XLA ops;
on the host, the benchmark's own spans (``TraceAnnotation`` names that
start with ``chipbench.``).  Everything after that works on ``Trace``
alone, so the tests drive it with a small recorded trace in JSON.
Times are nanoseconds on the profiler's clock, which host and device
events share.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Callable, Dict, Iterable, List, Optional, Tuple

SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = "/device:TPU:"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|ppermute|psum|send|recv", re.IGNORECASE)

Interval = Tuple[float, float]


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Tuple[float, float, str]]]  # device -> (start, end, op)
    host: List[Tuple[float, float, str]]  # benchmark spans (start, end, name)

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        return cls({k: [tuple(e) for e in v] for k, v in d["devices"].items()},
                   [tuple(e) for e in d["host"]])


def from_xspace(path: str, device_ids: Iterable[int]) -> Trace:
    """Read an ``.xplane.pb``: device planes are ``/device:TPU:<n>``, with
    their ops on the ``XLA Ops`` line, and only the planes of the cell's
    ``device_ids`` are kept; host spans come from every host line."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    keep = {f"{DEVICE_PLANE}{i}" for i in device_ids}
    devices: Dict[str, List[Tuple[float, float, str]]] = {}
    host: List[Tuple[float, float, str]] = []
    for plane in pd.planes:
        if plane.name in keep:
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.start_ns, e.end_ns, e.name) for e in line.events)
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        host.append((e.start_ns, e.end_ns, e.name))
    return Trace(devices, sorted(host))


# --------------------------------------------------------------------------
# interval arithmetic
# --------------------------------------------------------------------------


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out: List[List[float]] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of merged ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# --------------------------------------------------------------------------
# reductions
# --------------------------------------------------------------------------


def window(trace: Trace) -> Optional[Interval]:
    """The measured window: the benchmark's ``chipbench.window`` span."""
    spans = [(s, e) for s, e, n in trace.host if n == WINDOW_SPAN]
    return spans[-1] if spans else None


def device_busy(trace: Trace, dev: str, lo: float, hi: float,
                keep: Callable[[str], bool] = lambda name: True) -> List[Interval]:
    return merge(clip(((s, e) for s, e, n in trace.devices[dev] if keep(n)), lo, hi))


def busy_ns(trace: Trace, lo: float, hi: float) -> float:
    """Time some op ran, mean over devices."""
    if not trace.devices:
        return 0.0
    return sum(total(device_busy(trace, d, lo, hi)) for d in trace.devices) / len(trace.devices)


def idle_share(trace: Trace, lo: float, hi: float) -> Optional[float]:
    """1 - busy / window, mean over devices; None without a device."""
    if not trace.devices or hi <= lo:
        return None
    return 1.0 - busy_ns(trace, lo, hi) / (hi - lo)


def exposed_collective_share(trace: Trace, lo: float, hi: float) -> Optional[float]:
    """Share of the window in which a collective runs on a device and no
    other op does, mean over devices; None where no collective ran."""
    if not trace.devices or hi <= lo:
        return None
    shares, seen = [], False
    for d in trace.devices:
        coll = device_busy(trace, d, lo, hi, lambda n: bool(COLLECTIVE.search(n)))
        seen = seen or bool(coll)
        comp = device_busy(trace, d, lo, hi, lambda n: not COLLECTIVE.search(n))
        shares.append(total(subtract(coll, comp)) / (hi - lo))
    return sum(shares) / len(shares) if seen else None


def top_ops(trace: Trace, lo: float, hi: float, n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` op names with the most device time in the window, in
    seconds, mean over devices."""
    acc: Dict[str, float] = {}
    for d in trace.devices:
        for s, e, name in trace.devices[d]:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                acc[name] = acc.get(name, 0.0) + (e - s)
    nd = max(len(trace.devices), 1)
    ranked = sorted(acc.items(), key=lambda kv: (-kv[1], kv[0]))[:n]
    return [[name, ns / nd / 1e9] for name, ns in ranked]


def idle_gaps(trace: Trace, lo: float, hi: float, n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` longest device-idle gaps in the window (first device),
    each named by the benchmark span that covers most of it, in seconds."""
    if not trace.devices:
        return []
    dev = sorted(trace.devices)[0]
    gaps = subtract([(lo, hi)], device_busy(trace, dev, lo, hi))
    spans = [(s, e, name) for s, e, name in trace.host if name != WINDOW_SPAN]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        cover: Dict[str, float] = {}
        for hs, he, name in spans:
            ov = min(e, he) - max(s, hs)
            if ov > 0:
                cover[name] = cover.get(name, 0.0) + ov
        label = max(cover, key=cover.get) if cover else "no benchmark span"
        out.append([label, (e - s) / 1e9])
    return out
