"""The reduction from a profiler trace to device busy time, per-op time
and named idle gaps.

A traced run records the measured window inside a host annotation named
``window`` and the harness's own calls into the system inside further
annotations (``run_population``, ``result_sync``, ``submit``,
``drain``).  From the trace:

* busy time of a device: the union of the intervals of the events on its
  ``XLA Ops`` line, clipped to the window;
* time of an op: the sum of its events' durations clipped to the window,
  summed over the devices, with the number of events.  An op is named by
  its HLO instruction (``%sort_rows.4``); loops and calls, whose events
  span the ops of their bodies, count towards busy time only;
* idle gaps: the stretches of the window in which the first device ran
  nothing, each named by the harness annotation (or, failing that, the
  host event) that covers most of it.
"""

from __future__ import annotations

import contextlib
import pathlib
import re
from typing import Dict, Iterator, List, Optional, Tuple

import jax

#: the harness's own host annotations, in the order they nest
ANNOTATIONS = ("window", "run_population", "result_sync", "submit",
               "drain")

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
#: ops whose events enclose other ops' events
_CONTAINERS = re.compile(r"^%(while|conditional|call)\b")


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device event (``%fusion.3``)."""
    return event_name.split(" = ", 1)[0]


@contextlib.contextmanager
def capture(log_dir: pathlib.Path) -> Iterator[None]:
    """Profile the enclosed block into ``log_dir`` (device ops and host
    annotations; no Python function tracing)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load(log_dir: pathlib.Path):
    """The newest trace written under ``log_dir``."""
    from jax.profiler import ProfileData
    files = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no trace under {log_dir}")
    return ProfileData.from_file(str(files[-1]))


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


class Reduction:
    """What one trace says, over the ``window`` annotation."""

    def __init__(self, profile, devices: int):
        host: List[Tuple[str, float, float]] = []
        dev_events: Dict[int, List[Tuple[str, float, float]]] = {}
        for plane in profile.planes:
            m = _DEVICE.match(plane.name)
            if m and int(m.group(1)) < devices:
                evs = dev_events.setdefault(int(m.group(1)), [])
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        evs.extend((op_name(e.name), e.start_ns, e.end_ns)
                                   for e in line.events)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    host.extend((e.name, e.start_ns, e.end_ns)
                                for e in line.events if e.duration_ns > 0)
        windows = [(s, e) for n, s, e in host if n == "window"]
        if not windows:
            raise ValueError("the trace holds no 'window' annotation")
        self.start, self.end = max(windows, key=lambda w: w[1] - w[0])
        self.host = [(n, max(s, self.start), min(e, self.end))
                     for n, s, e in host if e > self.start and s < self.end]
        self.devices = devices
        self.ops: Dict[str, List[float]] = {}
        self.busy: Dict[int, List[Tuple[float, float]]] = {}
        for d in range(devices):
            clipped = [(n, max(s, self.start), min(e, self.end))
                       for n, s, e in dev_events.get(d, [])
                       if e > self.start and s < self.end]
            for n, s, e in clipped:
                if _CONTAINERS.match(n):
                    continue
                t = self.ops.setdefault(n, [0.0, 0])
                t[0] += (e - s) * 1e-9
                t[1] += 1
            self.busy[d] = _union([(s, e) for _, s, e in clipped])

    @property
    def window_s(self) -> float:
        return (self.end - self.start) * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices."""
        total = sum(e - s for iv in self.busy.values() for s, e in iv)
        return total * 1e-9 / max(self.devices, 1)

    def top_ops(self, k: int = 10) -> List[List]:
        ranked = sorted(self.ops.items(), key=lambda kv: -kv[1][0])
        return [[name, t] for name, (t, _) in ranked[:k]]

    def _host_during(self, s: float, e: float) -> str:
        """The host annotation that covers most of ``[s, e)``: the
        harness's own first, else any host event, else ``none``."""
        best: Dict[bool, Tuple[float, str]] = {}
        for n, hs, he in self.host:
            cover = min(he, e) - max(hs, s)
            if cover <= 0 or n == "window":
                continue
            ours = n in ANNOTATIONS
            if cover > best.get(ours, (0.0, ""))[0]:
                best[ours] = (cover, n)
        pick = best.get(True) or best.get(False)
        return pick[1] if pick else "none"

    def idle_gaps(self, k: int = 10) -> List[List]:
        """The ``k`` longest idle stretches of device 0, each named."""
        busy = self.busy.get(0, [])
        edges = [self.start] + [t for iv in busy for t in iv] + [self.end]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self._host_during(s, e), (e - s) * 1e-9]
                for s, e in gaps[:k]]


def reduce(log_dir: pathlib.Path, devices: int) -> Reduction:
    return Reduction(load(log_dir), devices)


def breakdown(red: Optional[Reduction]) -> Optional[Dict[str, List]]:
    if red is None:
        return None
    return {"device_ops": red.top_ops(), "idle_gaps": red.idle_gaps()}

