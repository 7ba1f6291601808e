"""The one reduction from a profiler trace to what the metrics read.

``reduce(path, spans)`` reads an ``.xplane.pb`` with ``jax.profiler
.ProfileData`` and returns a ``Reduced``:

* per device (``/device:TPU:<n>`` planes, their ``XLA Ops`` line): the
  union of op intervals inside the window (busy seconds), the idle gaps
  between them, each op's self time (its duration less that of the ops
  nested in it: a ``while`` holds its body's ops), and the time during
  which a collective ran while no other leaf op did (exposed collective);
* the window: the first host span named ``window``, or the extent of the
  device ops when there is none;
* each idle gap split by the harness's host spans (``spans``) over it,
  each piece named by the innermost span covering it, or ``none``.

Device and host events of one trace share the profiler's clock.
"""

from __future__ import annotations

import dataclasses
import re

COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?\(")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


@dataclasses.dataclass
class Device:
    busy_s: float
    self_s: dict          # op name -> [count, seconds]
    collective_exposed_s: float


@dataclasses.dataclass
class Reduced:
    window: tuple         # (start_ns, end_ns)
    devices: list         # [Device], by device index
    idle_by_span: dict    # span name -> idle seconds, summed over devices

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices."""
        return sum(d.busy_s for d in self.devices) / max(len(self.devices), 1)

    def op_seconds(self) -> dict:
        """Self seconds of each op, averaged over the devices."""
        total: dict = {}
        for d in self.devices:
            for name, (n, s) in d.self_s.items():
                c = total.setdefault(name, [0, 0.0])
                c[0] += n
                c[1] += s
        k = max(len(self.devices), 1)
        return {name: (n / k, s / k) for name, (n, s) in total.items()}

    def collective_exposed_s(self) -> float:
        return sum(d.collective_exposed_s for d in self.devices) / max(len(self.devices), 1)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1][1])[:top]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        k = max(len(self.devices), 1)
        return {"device_ops": [[short_name(n), s] for n, (_, s) in ops],
                "idle_gaps": [[n, s / k] for n, s in gaps]}


def short_name(op: str) -> str:
    """``%name = type kind(...)`` -> ``name kind type``, at most 120 chars."""
    m = re.match(r"%?(\S+) = (.+?) ([\w-]+)\(", op)
    if not m:
        return op[:120]
    name, shape, kind = m.groups()
    shape = re.sub(r"\{[^}]*\}", "", shape)
    return f"{name} {kind} {shape}"[:120]


def union(intervals) -> list:
    """Sorted, merged [(start, end)]."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a, b) -> list:
    """Merged intervals ``a`` less merged intervals ``b``."""
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


def nest(events):
    """[(start, end, name)] on one line -> (self seconds by name, leaf
    intervals, collective leaf intervals).  Events of a line nest."""
    evs = sorted(events, key=lambda x: (x[0], -(x[1] - x[0])))
    self_ns = [e - s for s, e, _ in evs]
    has_child = [False] * len(evs)
    stack: list = []
    for i, (s, e, _) in enumerate(evs):
        while stack and evs[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            p = stack[-1]
            self_ns[p] -= min(e, evs[p][1]) - s
            has_child[p] = True
        stack.append(i)
    self_s: dict = {}
    leaves, coll = [], []
    for i, (s, e, name) in enumerate(evs):
        c = self_s.setdefault(name, [0, 0.0])
        c[0] += 1
        c[1] += max(self_ns[i], 0) * 1e-9
        if not has_child[i]:
            (coll if COLLECTIVE.search(name) else leaves).append((s, e))
    return self_s, leaves, coll


def reduce_device(events, window, segs) -> tuple[Device, dict]:
    lo, hi = window
    inside = [(max(s, lo), min(e, hi), n) for s, e, n in events if e > lo and s < hi]
    busy = union((s, e) for s, e, _ in inside)
    gaps = subtract([(lo, hi)], busy)
    self_s, leaves, coll = nest(inside)
    exposed = subtract(union(coll), union(leaves))
    idle: dict = {}
    for name, ns in attribute(gaps, segs):
        idle[name] = idle.get(name, 0.0) + ns * 1e-9
    dev = Device(busy_s=length(busy) * 1e-9, self_s=self_s,
                 collective_exposed_s=length(exposed) * 1e-9)
    return dev, idle


def segments(spans) -> list:
    """Nested host spans -> sorted, disjoint [(start, end, innermost name)]."""
    out: list = []
    stack: list = []        # [(end, name)], innermost last
    cur = None

    def emit(until):
        nonlocal cur
        if stack and cur is not None and until > cur:
            out.append((cur, until, stack[-1][1]))
        cur = until

    for s, e, n in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            emit(stack[-1][0])
            stack.pop()
        emit(s)
        stack.append((e, n))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return out


def attribute(gaps, segs) -> list:
    """Split sorted gaps by the disjoint span segments over them: each
    piece goes to its segment's span, the rest to ``none``.
    -> [(span name, ns)]."""
    out, j = [], 0
    for lo, hi in gaps:
        covered = 0.0
        while j < len(segs) and segs[j][1] <= lo:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < hi:
            ov = min(hi, segs[k][1]) - max(lo, segs[k][0])
            if ov > 0:
                out.append((segs[k][2], ov))
                covered += ov
            k += 1
        if hi - lo > covered:
            out.append(("none", hi - lo - covered))
    return out


def read(path: str, span_names) -> tuple[dict, list]:
    """-> ({device index: [(start_ns, end_ns, op name)]},
    [(start_ns, end_ns, span name)] of the named host spans)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: dict = {}
    spans: list = []
    names = set(span_names)
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[int(m.group(1))] = [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in names:
                        spans.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    return devices, spans


def reduce(path: str, span_names=("window", "fit", "data_fn", "drain", "tick",
                                  "admit", "prefill", "decode", "submit", "wait",
                                  "gc")) -> Reduced:
    devices, spans = read(path, span_names)
    if not devices:
        raise ValueError(f"{path}: no TPU device plane with XLA Ops")
    win = [(s, e) for s, e, n in spans if n == "window"]
    if win:
        window = win[0]
    else:
        window = (min(s for evs in devices.values() for s, _, _ in evs),
                  max(e for evs in devices.values() for _, e, _ in evs))
    segs = segments([sp for sp in spans if sp[2] != "window"])
    reduced, idle = [], {}
    for idx in sorted(devices):
        dev, dev_idle = reduce_device(devices[idx], window, segs)
        reduced.append(dev)
        for k, v in dev_idle.items():
            idle[k] = idle.get(k, 0.0) + v
    return Reduced(window=window, devices=reduced, idle_by_span=idle)


def find_trace(log_dir: str) -> str:
    import glob

    paths = sorted(glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]
