"""Device idle time under the program's own spans.

The program marks its host work with profiler annotations (``stack.*``,
``hadoop.*``, ``engine.*``, ``serve.*``), which a traced run loads into
``Reduction.host`` beside the harness's annotations, on the device
trace's clock.  Here they meet the idle stretches of device 0 (the
window minus the union of its ops, ``Reduction.busy[0]``): the share of
the window in which the device ran nothing while the host was inside
given spans, and the partition of all idle time by the innermost program
span around it.

Intervals are ``(start_ns, end_ns)`` lists, sorted and disjoint.

    python bench/spans.py --workload <cell> --seed <n> --seconds <s>

runs the cell traced, as ``bench/run.py --trace 1`` does, and prints one
more line: the partition of its idle time by innermost span, in percent
of the window, and of its ten longest idle gaps, in milliseconds.
"""

from __future__ import annotations

import json
import pathlib
import sys
from typing import Dict, Iterable, List, Optional, Tuple

Intervals = List[Tuple[float, float]]

#: name prefixes of the program's spans (the harness's have none)
PROGRAM = ("stack.", "hadoop.", "engine.", "serve.")


def union(intervals: Iterable[Tuple[float, float]]) -> Intervals:
    out: Intervals = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def intersect(a: Intervals, b: Intervals) -> Intervals:
    out: Intervals = []
    i = j = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: Intervals, b: Intervals) -> Intervals:
    """``a`` without ``b``."""
    out: Intervals = []
    j = 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append((s, e))
    return out


def length(a: Intervals) -> float:
    return sum(e - s for s, e in a)


def idle(red) -> Intervals:
    """The stretches of the window in which device 0 ran nothing."""
    return subtract([(red.start, red.end)], red.busy.get(0, []))


def spans(red, names: Iterable[str]) -> Intervals:
    """The union of the host spans named in ``names``."""
    names = set(names)
    return union((s, e) for n, s, e in red.host if n in names)


def idle_share(red, names: Iterable[str],
               minus: Iterable[str] = ()) -> Optional[float]:
    """Percent of the window in which device 0 ran nothing and the host
    was inside a span named in ``names`` but in none named in ``minus``;
    ``None`` where the trace holds no span of ``names``."""
    inside = spans(red, names)
    if not inside:
        return None
    part = subtract(intersect(idle(red), inside), spans(red, minus))
    return 100.0 * length(part) / (red.end - red.start)


def partition(red, gaps: Intervals) -> Dict[str, float]:
    """Nanoseconds of ``gaps`` under each innermost program span (the one
    that began last among those open, on any thread), and under
    ``none`` where no program span is open, largest first."""
    prog = [(s, e, n) for n, s, e in red.host if n.startswith(PROGRAM)]
    points = sorted({t for s, e, _ in prog for t in (s, e)}
                    | {t for iv in gaps for t in iv})
    starts = sorted(prog)
    out: Dict[str, float] = {}
    active: List[Tuple[float, float, str]] = []
    nxt = g = 0
    for a, b in zip(points, points[1:]):
        while nxt < len(starts) and starts[nxt][0] <= a:
            active.append(starts[nxt])
            nxt += 1
        active = [sp for sp in active if sp[1] > a]
        while g < len(gaps) and gaps[g][1] <= a:
            g += 1
        if g == len(gaps) or gaps[g][0] > a:
            continue                          # outside the gaps
        name = max(active, key=lambda sp: (sp[0], -sp[1]))[2] \
            if active else "none"
        out[name] = out.get(name, 0.0) + b - a
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def idle_by_span(red) -> Dict[str, float]:
    """Percent of the window idle under each innermost program span; the
    parts sum to the idle share."""
    window = red.end - red.start
    return {n: 100.0 * t / window
            for n, t in partition(red, idle(red)).items()}


def longest_gaps(red, k: int = 10) -> List[Dict[str, float]]:
    """The ``k`` longest idle stretches of device 0, each in milliseconds
    with its milliseconds under each innermost program span."""
    gaps = sorted(idle(red), key=lambda g: g[0] - g[1])[:k]
    return [{"ms": (e - s) * 1e-6,
             "spans": {n: t * 1e-6
                       for n, t in partition(red, [(s, e)]).items()}}
            for s, e in gaps]


def main(argv: List[str]) -> int:
    root = pathlib.Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root), str(root / "src")]
    from bench import harness, tracing
    seen = []
    reduce = tracing.reduce

    def keep(*args):
        seen.append(reduce(*args))
        return seen[-1]

    tracing.reduce = keep
    try:
        harness.run_cell(argv + ["--trace", "1"], root=root)
    except harness.NoAccelerator as e:
        print(f"error: {e}", file=sys.stderr)
        return 3
    print(json.dumps({"idle_by_span": idle_by_span(seen[-1]),
                      "longest_gaps": longest_gaps(seen[-1])}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
