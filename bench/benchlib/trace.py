"""Reduce a profiler trace (``.xplane.pb``) to device time.

The TPU trace has one plane per chip (``/device:TPU:<i>``); its ``XLA Ops``
line holds every HLO instruction that ran, named by the instruction's text
(``%quantize_op.11 = (...) custom-call(...)``), nested where a ``while``
holds its body.  ``Async XLA Ops`` holds asynchronous starts.  The host
plane holds the benchmark's own spans (``window``, ``feed``, ``dispatch``,
``block``).

* busy: the union of the intervals of ``XLA Ops`` events on a chip;
* window: the host ``window`` span's length;
* self time: an event's duration less what the events nested in it cover;
* collectives: events whose instruction is an all-reduce, all-gather,
  reduce-scatter, collective-permute or all-to-all (sync, start or done),
  on either ops line; their exposed part is what no other leaf event
  covers on that chip.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
HOST_PLANE = "/host:CPU"
HOST_SPANS = ("window", "feed", "dispatch", "block")
_INSTR = re.compile(r"^%([^\s=]+) = ")
_COLLECTIVE = re.compile(r"\b(all-reduce|all-gather|reduce-scatter|"
                         r"collective-permute|all-to-all)(-start|-done)?\(")


@dataclass(frozen=True)
class Event:
    name: str          # instruction name, e.g. "quantize_op.11"
    start: float       # ns
    end: float         # ns
    collective: bool = False


@dataclass
class DeviceOps:
    ops: List[Event]                    # the XLA Ops line, sorted
    async_ops: List[Event] = field(default_factory=list)


def instr_name(text: str) -> str:
    m = _INSTR.match(text)
    return m.group(1) if m else text


def op_kind(name: str) -> str:
    """An instruction's name without its numeric suffix."""
    return re.sub(r"\.\d+$", "", name)


def is_collective(text: str) -> bool:
    return bool(_COLLECTIVE.search(text))


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals: Sequence[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Tuple[float, float]],
             b: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """``a`` less ``b``; both unions (sorted, disjoint)."""
    out: List[Tuple[float, float]] = []
    j = 0
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


def self_times(ops: Sequence[Event]) -> List[Tuple[Event, float, bool]]:
    """(event, self time, is leaf) for events nested by their intervals."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i].start, -ops[i].end))
    child = [0.0] * len(ops)
    leaf = [True] * len(ops)
    stack: List[int] = []
    for i in order:
        ev = ops[i]
        while stack and ops[stack[-1]].end <= ev.start:
            stack.pop()
        if stack and ev.end <= ops[stack[-1]].end:
            child[stack[-1]] += ev.end - ev.start
            leaf[stack[-1]] = False
        stack.append(i)
    return [(ops[i], max(0.0, ops[i].end - ops[i].start - child[i]), leaf[i])
            for i in range(len(ops))]


class Reduced:
    """A trace reduced to what the per-layer metrics read."""

    def __init__(self, devices: Dict[int, DeviceOps],
                 host: List[Tuple[str, float, float]]):
        if not devices:
            raise ValueError("the trace holds no TPU device plane")
        self.devices = devices
        self.host = sorted(host, key=lambda h: h[1])
        windows = [h for h in self.host if h[0] == "window"]
        if len(windows) != 1:
            raise ValueError(f"expected one host 'window' span, found "
                             f"{len(windows)}")
        self.window = windows[0][1:]
        self._self = {d: self_times(v.ops) for d, v in devices.items()}

    def _mean(self, per_device) -> float:
        vals = [per_device(d) for d in sorted(self.devices)]
        return sum(vals) / len(vals)

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over chips."""
        return self._mean(lambda d: length(union(
            (e.start, e.end) for e in self.devices[d].ops))) * 1e-9

    def op_seconds(self, pattern: str) -> float:
        """Self seconds of the instructions whose name matches ``pattern``
        (a regular expression, matched at the start), mean over chips."""
        rx = re.compile(pattern)
        return self._mean(lambda d: sum(
            t for e, t, _ in self._self[d] if rx.match(e.name))) * 1e-9

    def _collectives(self, d: int) -> List[Tuple[float, float]]:
        dev = self.devices[d]
        return union((e.start, e.end) for e in dev.ops + dev.async_ops
                     if e.collective)

    def collective_s(self) -> float:
        return self._mean(lambda d: length(self._collectives(d))) * 1e-9

    def exposed_collective_s(self) -> float:
        def exposed(d):
            compute = union((e.start, e.end) for e, _, leaf in self._self[d]
                            if leaf and not e.collective)
            return length(subtract(self._collectives(d), compute))
        return self._mean(exposed) * 1e-9

    def breakdown(self, top: int = 10) -> Dict[str, List]:
        """The device operations that took most self time (by instruction
        kind, summed over chips and divided by their number) and the
        longest idle gaps of the first chip, each named by the host spans
        running at its midpoint."""
        n = len(self.devices)
        kinds: Dict[str, float] = {}
        for d in self.devices:
            for e, t, _ in self._self[d]:
                kinds[op_kind(e.name)] = kinds.get(op_kind(e.name), 0.0) + t
        ops = sorted(kinds.items(), key=lambda kv: -kv[1])[:top]
        d0 = min(self.devices)
        busy = union((e.start, e.end) for e in self.devices[d0].ops)
        gaps = [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)]
        gaps.sort(key=lambda g: g[0] - g[1])
        shift = self._clock_shift(d0)
        named = []
        for s, e in gaps[:top]:
            mid = (s + e) / 2 + shift
            spans = [h[0] for h in self.host
                     if h[0] != "window" and h[1] <= mid <= h[2]]
            label = "+".join(spans) if spans else "between host spans"
            named.append([label, (e - s) * 1e-9])
        return {"device_ops": [[k, v / n * 1e-9] for k, v in ops],
                "idle_gaps": named}

    def _clock_shift(self, d: int) -> float:
        """Device time -> host time: the median offset between the end of
        each ``block`` span (the host sees the step's outputs) and the end
        of the device's last operation before it."""
        ends = sorted(e for _, e in union(
            (e.start, e.end) for e in self.devices[d].ops))
        blocks = [h[2] for h in self.host if h[0] == "block"]
        offs = []
        for b in blocks:
            before = [x for x in ends if x <= b]
            if before:
                offs.append(b - before[-1])
        if not offs:
            return 0.0
        offs.sort()
        return offs[len(offs) // 2]


def _events(line) -> List[Event]:
    out = []
    for e in line.events:
        start = float(e.start_ns)
        out.append(Event(instr_name(e.name), start,
                         start + float(e.duration_ns), is_collective(e.name)))
    out.sort(key=lambda ev: (ev.start, -ev.end))
    return out


def from_profile(pd, device_ids: Optional[Iterable[int]] = None) -> Reduced:
    devices: Dict[int, DeviceOps] = {}
    host: List[Tuple[str, float, float]] = []
    wanted = set(device_ids) if device_ids is not None else None
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            idx = int(m.group(1))
            if wanted is not None and idx not in wanted:
                continue
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE not in lines:
                continue
            devices[idx] = DeviceOps(
                _events(lines[OPS_LINE]),
                _events(lines[ASYNC_LINE]) if ASYNC_LINE in lines else [])
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                for e in ln.events:
                    if e.name in HOST_SPANS:
                        s = float(e.start_ns)
                        host.append((e.name, s, s + float(e.duration_ns)))
    return Reduced(devices, host)


def load(logdir: str, devices=None) -> Reduced:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {logdir}, "
                                f"found {paths}")
    ids = [d.id for d in devices] if devices is not None else None
    return from_profile(ProfileData.from_file(paths[0]), ids)
