"""Reduction of a profiler trace to the benchmark's device numbers.

A trace is read once into plain lists: per device, its operations as
``(name, start_ns, end_ns, text)`` (the HLO instruction's name; a Pallas
kernel's is its jitted wrapper's, ``_fastmix_track_fused.7``), and the
benchmark's own host spans (``jax.profiler.TraceAnnotation`` names that
start with ``bench.``).  Device and host events share the trace's clock.
From those lists:

* busy time: the union of a device's operation intervals inside the
  window, so overlapping operations count once;
* kernel time: the summed durations of the operations whose text names a
  kernel;
* idle gaps: the stretches of the window in which a device runs nothing,
  each charged to the innermost host span open at its midpoint;
* exposed collective time: the part of the union of a device's collective
  operations during which no other operation runs on it.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
from typing import Dict, List, Optional, Sequence, Tuple

#: substrings that mark an operation as a collective
COLLECTIVES = ("collective-permute", "all-gather", "all-reduce",
               "all-to-all", "reduce-scatter", "collective-broadcast")
#: host spans the benchmark writes start with this
SPAN_PREFIX = "bench."
#: the device line that holds one event per XLA operation
OPS_LINE = "XLA Ops"
#: the device line of asynchronous operations (copies, collectives) in flight
ASYNC_LINE = "Async XLA Ops"

Op = Tuple[str, int, int, str]
Span = Tuple[str, int, int]


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Op]]
    spans: List[Span]

    # ------------------------------------------------------------- window
    def window(self) -> Tuple[int, int]:
        """The ``bench.window`` span, or else the extent of all spans."""
        wins = [s for s in self.spans if s[0] == SPAN_PREFIX + "window"]
        if wins:
            return wins[0][1], wins[0][2]
        return (min(s[1] for s in self.spans), max(s[2] for s in self.spans))

    def window_s(self) -> float:
        w0, w1 = self.window()
        return (w1 - w0) * 1e-9

    def ops(self, device: str) -> List[Op]:
        """The device's operations that run on its cores (no ``async``)."""
        return [o for o in self.devices[device] if not is_async(o)]

    # --------------------------------------------------------------- busy
    def busy_s(self, device: str) -> float:
        w0, w1 = self.window()
        return union_ns(clip([(o[1], o[2]) for o in self.ops(device)],
                             w0, w1)) * 1e-9

    def mean_busy_s(self) -> float:
        return sum(self.busy_s(d) for d in self.devices) / len(self.devices)

    def idle_share(self) -> float:
        """1 - busy / window, as a mean over the devices (a fraction)."""
        return 1.0 - self.mean_busy_s() / self.window_s()

    # ------------------------------------------------------------ kernels
    def kernel_s(self, kernel: str) -> float:
        """Summed device time of operations whose text names ``kernel``,
        over every device, inside the window."""
        w0, w1 = self.window()
        return 1e-9 * sum(max(0, min(e, w1) - max(s, w0))
                          for d in self.devices
                          for _, s, e, text in self.ops(d) if kernel in text)

    def top_ops(self, n: int = 10) -> List[List]:
        """``[name, seconds]`` of the operations that took most device time
        (summed over devices and over every event of the name)."""
        w0, w1 = self.window()
        acc: Dict[str, int] = {}
        for d in self.devices:
            for name, s, e, _ in self.ops(d):
                dur = min(e, w1) - max(s, w0)
                if dur > 0:
                    acc[name] = acc.get(name, 0) + dur
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in top]

    # --------------------------------------------------------- idle gaps
    def idle_gaps(self) -> List[Tuple[str, int, int]]:
        """Every idle stretch of every device in the window, with the
        innermost host span open at its midpoint (``(no span)`` if none)."""
        w0, w1 = self.window()
        out = []
        inner = [s for s in self.spans if s[0] != SPAN_PREFIX + "window"]
        for d in self.devices:
            merged = merge(clip([(o[1], o[2]) for o in self.ops(d)], w0, w1))
            cursor = w0
            for s, e in merged + [(w1, w1)]:
                if s > cursor:
                    out.append((span_at(inner, (cursor + s) // 2),
                                cursor, s))
                cursor = max(cursor, e)
        return out

    def idle_by_span(self, n: int = 10) -> List[List]:
        """``[host span, seconds]``: idle device time charged to each host
        span, summed over devices, largest first."""
        acc: Dict[str, int] = {}
        for name, s, e in self.idle_gaps():
            acc[name] = acc.get(name, 0) + (e - s)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in top]

    # ------------------------------------------------------- collectives
    def exposed_collective_s(self, device: str) -> float:
        """Time in which a collective (on the core or in flight) is active
        on the device and no other operation runs on its cores."""
        w0, w1 = self.window()
        coll = [(s, e) for _, s, e, text in self.devices[device]
                if is_collective(text)]
        comp = [(s, e) for _, s, e, text in self.ops(device)
                if not is_collective(text)]
        return subtract_ns(merge(clip(coll, w0, w1)),
                           merge(clip(comp, w0, w1))) * 1e-9

    def collective_share(self) -> Optional[float]:
        """Exposed collective time over busy time, summed over devices (a
        fraction); ``None`` where no collective ran."""
        if not any(is_collective(o[3]) for ops in self.devices.values()
                   for o in ops):
            return None
        busy = sum(self.busy_s(d) for d in self.devices)
        return sum(self.exposed_collective_s(d)
                   for d in self.devices) / busy

    def breakdown(self) -> Dict[str, List[List]]:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_by_span()}

    # ------------------------------------------------------------- files
    def to_json(self) -> Dict:
        return {"devices": self.devices, "spans": self.spans}

    @classmethod
    def from_json(cls, obj: Dict) -> "Trace":
        return cls(devices={k: [tuple(o) for o in v]
                            for k, v in obj["devices"].items()},
                   spans=[tuple(s) for s in obj["spans"]])


# ------------------------------------------------------------- intervals
def is_collective(text: str) -> bool:
    return any(c in text for c in COLLECTIVES)


def is_async(op: Op) -> bool:
    return op[3].endswith(" async")


def clip(iv: Sequence[Tuple[int, int]], w0: int, w1: int):
    return [(max(s, w0), min(e, w1)) for s, e in iv if e > w0 and s < w1]


def merge(iv: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_ns(iv: Sequence[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in merge(iv))


def subtract_ns(a: List[Tuple[int, int]], b: List[Tuple[int, int]]) -> int:
    """Length of the merged intervals ``a`` not covered by merged ``b``."""
    total, j = 0, 0
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


def span_at(spans: Sequence[Span], t: int) -> str:
    """The innermost (shortest) span open at ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "(no span)"


# --------------------------------------------------------------- loading
def op_name(event_name: str) -> str:
    """An XLA op event is named by its whole HLO instruction
    (``%fusion.301 = f32[...] fusion(...)``); keep the instruction's name."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _leaves(ops: List[Op]) -> List[Op]:
    """The ops that contain no other op of the line (a ``while`` of a scan
    spans every op of its body)."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    parent = [False] * len(ops)
    stack: List[int] = []
    for i, (_, s, e, _) in enumerate(ops):
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][2]:
            parent[stack[-1]] = True
        stack.append(i)
    return [o for o, p in zip(ops, parent) if not p]


def from_xplane(path: str, n_devices: Optional[int] = None) -> Trace:
    """Read one ``.xplane.pb`` into a :class:`Trace`, keeping the first
    ``n_devices`` accelerator planes (all of them by default): the leaf
    ops of the ``XLA Ops`` line, with the ``Async XLA Ops`` line's events
    (copies and collectives in flight) marked ``async`` in their text."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Op]] = {}
    spans: List[Span] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops: List[Op] = []
            for ln in plane.lines:
                if ln.name not in (OPS_LINE, ASYNC_LINE):
                    continue
                tag = " async" if ln.name == ASYNC_LINE else ""
                for ev in ln.events:
                    name = op_name(ev.name)
                    ops.append((name, int(ev.start_ns), int(ev.end_ns),
                                name + tag))
            if ops:
                sync = _leaves([o for o in ops if not o[3].endswith("async")])
                devices[plane.name] = sync + [o for o in ops
                                              if o[3].endswith("async")]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, int(ev.start_ns),
                                      int(ev.end_ns)))
    keep = sorted(devices, key=_device_order)[:n_devices]
    return Trace(devices={k: devices[k] for k in keep}, spans=spans)


def _device_order(name: str):
    tail = name.rsplit(":", 1)[-1]
    return (0, int(tail), name) if tail.isdigit() else (1, 0, name)


def newest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def save(trace: Trace, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace.to_json(), f)


def load(path: str) -> Trace:
    with gzip.open(path, "rt") as f:
        return Trace.from_json(json.load(f))
