"""From a profiler trace to device busy time, kernel time and idle gaps.

The run wraps its measured window in a host annotation named
``bench.window`` and traces it with ``jax.profiler``.  The reduction reads
the ``.xplane.pb`` file with ``jax.profiler.ProfileData``:

* device planes are those named ``/device:<PLATFORM>:<n>``; on each, the
  line of XLA operations (``XLA Ops``) holds one event per operation run,
  named by its HLO instruction text (``%name = shape opcode(...)``);
  control flow (``while``, ``conditional``, ``call``) spans the operations
  it runs and is left out, so each interval is counted once;
* busy time is the union of those events' intervals inside the window,
  averaged over the device planes; idle share is 1 - busy / window;
* kernel time is the sum of the durations of the events whose name
  matches a pattern; the names matched, and the plane, are logged, so a
  renamed kernel shows as a metric left out and never as 0;
* each of the ``GAPS_NAMED`` longest idle gaps is named after the
  innermost host event that covers its middle.
"""
from __future__ import annotations

import glob
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
HOST_PLANE = re.compile(r"^/host:")
# host events that only say a thread pool woke up
HOST_NOISE = re.compile(r"^(ThreadpoolListener|SlinkyThreadPool)")
HLO = re.compile(r"^(%[\w.\-]+) = (.*?[\]}\)]) ([a-z][\w\-]*)\(")
LAYOUT = re.compile(r"\{[^{}]*\}")
CONTAINERS = ("while", "conditional", "call")
GAPS_NAMED = 2000


def short(name: str) -> str:
    """``%name opcode shape`` of an HLO instruction's text (the layouts
    dropped), or the name itself when it is not one."""
    m = HLO.match(name)
    if not m:
        return name[:120]
    inst, shape, op = m.groups()
    if "tpu_custom_call" in name:
        op += "[tpu_custom_call]"
    return f"{inst} {op} {LAYOUT.sub('', shape)}"[:120]


def opcode(name: str) -> str:
    m = HLO.match(name)
    return m.group(3) if m else ""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


class TraceSummary:
    """The reduction of one traced window (times in seconds)."""

    def __init__(self, window: Tuple[float, float], planes: Dict[str, dict],
                 host: List[Tuple[str, float, float]]):
        self.window = window
        self.planes = planes        # name -> {"busy": [(s, e)], "ops": {name: ns}}
        self.host = host            # (name, start, end), ns
        self.window_s = (window[1] - window[0]) / 1e9
        n = max(len(planes), 1)
        self.busy_s = sum(sum(e - s for s, e in p["busy"])
                          for p in planes.values()) / n / 1e9

    @classmethod
    def from_file(cls, path: str) -> "TraceSummary":
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        window = None
        host = []
        raw = {}
        for plane in pd.planes:
            if HOST_PLANE.match(plane.name):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name == WINDOW:
                            window = (ev.start_ns, ev.end_ns)
                        elif not HOST_NOISE.match(ev.name):
                            host.append((ev.name, ev.start_ns, ev.end_ns))
            elif DEVICE_PLANE.match(plane.name):
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        raw[plane.name] = [(ev.name, ev.start_ns, ev.end_ns)
                                           for ev in line.events]
        if window is None:
            raise RuntimeError(f"no {WINDOW!r} annotation in {path}")
        if not raw:
            raise RuntimeError(f"no device plane with an {OPS_LINE!r} line "
                               f"in {path}")
        ws, we = window
        planes = {}
        for name, evs in raw.items():
            ops: Dict[str, float] = {}
            calls: Dict[str, int] = {}
            spans = []
            for op, s, e in evs:
                s, e = max(s, ws), min(e, we)
                if e <= s or opcode(op) in CONTAINERS:
                    continue
                ops[op] = ops.get(op, 0.0) + (e - s)
                calls[op] = calls.get(op, 0) + 1
                spans.append((s, e))
            planes[name] = {"busy": _union(spans), "ops": ops}
            log(f"[trace] plane {name}: {len(spans)} operations in the "
                f"window, {len(ops)} distinct names; the longest:")
            for op, ns in sorted(ops.items(), key=lambda kv: -kv[1])[:25]:
                log(f"[trace]   {ns / 1e9:.6f} s in {calls[op]} calls  "
                    f"{short(op)}")
        return cls(window, planes, host)

    @classmethod
    def from_dir(cls, trace_dir: str) -> "TraceSummary":
        files = sorted(glob.glob(os.path.join(
            trace_dir, "**", "*.xplane.pb"), recursive=True))
        if not files:
            raise RuntimeError(f"no .xplane.pb under {trace_dir}")
        return cls.from_file(files[-1])

    def ops(self) -> Dict[str, float]:
        """Seconds per operation name, averaged over the device planes."""
        n = max(len(self.planes), 1)
        out: Dict[str, float] = {}
        for p in self.planes.values():
            for op, ns in p["ops"].items():
                out[op] = out.get(op, 0.0) + ns / n / 1e9
        return out

    def kernel_seconds(self, pattern: str, label: str) -> Optional[float]:
        """Seconds of the operations whose name matches ``pattern``
        (averaged over devices), or None where none matches."""
        rx = re.compile(pattern)
        hit = {op: s for op, s in self.ops().items() if rx.search(op)}
        if not hit:
            log(f"[trace] {label}: no operation matches {pattern!r} on "
                f"{sorted(self.planes)}")
            return None
        log(f"[trace] {label}: {sum(hit.values())} s in {len(hit)} "
            f"operation name(s) matching {pattern!r} on "
            f"{sorted(self.planes)}: {sorted(map(short, hit))[:8]}")
        return sum(hit.values())

    def gaps(self) -> List[Tuple[float, float]]:
        """Idle intervals of the first device plane inside the window."""
        p = self.planes[sorted(self.planes)[0]]
        out, t = [], self.window[0]
        for s, e in p["busy"]:
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if self.window[1] > t:
            out.append((t, self.window[1]))
        return out

    def host_names(self, times: List[float]) -> List[str]:
        """For each time, the innermost (shortest) host event covering it:
        one sweep over the host events in order of their start."""
        import heapq
        events = sorted(self.host, key=lambda ev: ev[1])
        order = sorted(range(len(times)), key=lambda i: times[i])
        out = ["(no host event)"] * len(times)
        active: List[Tuple[float, float, str]] = []     # (end, length, name)
        j = 0
        for i in order:
            t = times[i]
            while j < len(events) and events[j][1] <= t:
                name, s, e = events[j]
                heapq.heappush(active, (e, e - s, name))
                j += 1
            while active and active[0][0] < t:
                heapq.heappop(active)
            if active:
                out[i] = min(active, key=lambda a: a[1])[2]
        return out

    def breakdown(self) -> dict:
        ops = sorted(self.ops().items(), key=lambda kv: -kv[1])[:10]
        ops = [(short(k), v) for k, v in ops]
        # the longest gaps, each named by what the host was doing then
        gaps = sorted(self.gaps(), key=lambda g: g[0] - g[1])[:GAPS_NAMED]
        named: Dict[str, float] = {}
        for (s, e), name in zip(gaps, self.host_names(
                [(s + e) / 2 for s, e in gaps])):
            named[name] = named.get(name, 0.0) + (e - s) / 1e9
        gaps = sorted(named.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}
