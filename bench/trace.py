"""From a profiler trace of the window to the device's busy time, idle
gaps, top operations and exposed collective time.

`load` reads the newest `.xplane.pb` that `jax.profiler` wrote; `reduce`
turns it into a `Reduced`. The arithmetic lives in plain functions over
(start, end, name) tuples, so that it can be checked on a synthesized
trace:
  busy       the union of the intervals in which an operation ran on a
             device, inside the window, averaged over the chips used;
  idle gaps  the longest holes in device 0's busy union, each named by
             the host annotation (bench.build, bench.dispatch,
             bench.epoch_end) that covers most of it and at least half,
             else "other";
  exposed    collective operations' time during which no other operation
             ran on that device, averaged over the chips, per step.
"""
from __future__ import annotations

import dataclasses
import pathlib
import re
import shutil
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float, str]        # (start s, end s, name)

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
# an op is named by its HLO text, "%name = type opcode(operands)"; a
# collective is one whose name or opcode is a collective's, not one that
# merely takes a collective's result as an operand
_KINDS = r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
COLLECTIVE = re.compile(rf"^%?{_KINDS}|[\s)]{_KINDS}(-start|-done)?\(")
NAME_CHARS = 160
HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
TOP = 10


def clear(directory: pathlib.Path) -> None:
    shutil.rmtree(directory, ignore_errors=True)
    pathlib.Path(directory).mkdir(parents=True)


def start(directory: pathlib.Path) -> None:
    """Trace the device and the host's annotations; the Python tracer
    stays off, as it would slow the host loop being measured."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(directory), profiler_options=options)


def load(directory: pathlib.Path):
    import jax
    files = sorted(pathlib.Path(directory).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no profiler trace under {directory}")
    return jax.profiler.ProfileData.from_file(str(files[-1]))


def events(profile) -> Tuple[Dict[int, List[Interval]], List[Interval]]:
    """(device id -> its operations, host annotations of the harness).
    A device's operations are its XLA ops and its asynchronous
    collectives; other asynchronous ops (copies, slices) overlap them."""
    ops: Dict[int, List[Interval]] = {}
    host: List[Interval] = []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, ASYNC_LINE):
                ops.setdefault(int(m.group(1)), []).extend(
                    (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                     e.name[:NAME_CHARS]) for e in line.events
                    if line.name == OPS_LINE or COLLECTIVE.search(e.name))
            elif not m:
                host.extend(
                    (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                     e.name) for e in line.events
                    if e.name.startswith(HOST_PREFIX))
    return ops, host


def union(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted((s, e) for s, e, *_ in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(merged: Sequence[Tuple[float, float]]) -> float:
    return float(sum(e - s for s, e in merged))


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi), n) for s, e, n in intervals
            if e > lo and s < hi]


def subtract(a: Sequence[Tuple[float, float]],
             b: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """The parts of merged intervals `a` that merged `b` leaves free."""
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


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    return subtract([(lo, hi)], busy)


def label_gap(gap: Tuple[float, float], host: Sequence[Interval]) -> str:
    """The harness annotation that covers most of `gap`, if it covers at
    least half of it, else other."""
    best, cover = "other", 0.5 * (gap[1] - gap[0])
    for s, e, name in host:
        c = min(e, gap[1]) - max(s, gap[0])
        if c >= cover and name != WINDOW_SPAN:
            best, cover = name[len(HOST_PREFIX):], c
    return best


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                       # mean over the chips used
    steps: int
    exposed_collective_s: Optional[float]   # mean over chips; None: none
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> Dict:
        return {"device_ops": [list(x) for x in self.top_ops],
                "idle_gaps": [list(x) for x in self.idle_gaps]}


def reduce_events(ops: Dict[int, List[Interval]], host: List[Interval],
                  device_ids: Sequence[int], steps: int) -> Reduced:
    """The window is the harness's `bench.window` span, or where there is
    none, the extent of the chips' operations."""
    spans = [(s, e) for s, e, n in host if n == WINDOW_SPAN]
    if spans:
        lo, hi = spans[0]
    else:
        every = [x for d in device_ids for x in ops.get(d, [])]
        lo = min(s for s, _, _ in every)
        hi = max(e for _, e, _ in every)
    busy, exposed, totals = [], [], {}
    any_collective = False
    for d in device_ids:
        mine = clip(ops.get(d, []), lo, hi)
        busy.append(length(union(mine)))
        coll = [x for x in mine if COLLECTIVE.search(x[2])]
        other = [x for x in mine if not COLLECTIVE.search(x[2])]
        any_collective |= bool(coll)
        exposed.append(length(subtract(union(coll), union(other))))
        for s, e, n in mine:
            totals[n] = totals.get(n, 0.0) + (e - s) / len(device_ids)
    first = clip(ops.get(device_ids[0], []), lo, hi)
    longest = sorted(gaps(union(first), lo, hi),
                     key=lambda g: g[0] - g[1])[:TOP]
    named = [(label_gap(g, host), g[1] - g[0]) for g in longest]
    return Reduced(
        window_s=hi - lo, busy_s=sum(busy) / len(busy), steps=steps,
        exposed_collective_s=(sum(exposed) / len(exposed)
                              if any_collective else None),
        top_ops=sorted(totals.items(), key=lambda x: -x[1])[:TOP],
        idle_gaps=named)


def reduce(profile, device_ids: Sequence[int], steps: int) -> Reduced:
    ops, host = events(profile)
    return reduce_events(ops, host, device_ids, steps)
