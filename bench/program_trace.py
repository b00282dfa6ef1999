"""The program's own spans and device scopes in a profiler trace.

`repro.runtime.tracing` names them: host spans "repro.<name>" with their
counts as stats, and device ops under `jax.named_scope` scopes. This
module reads them back from the trace a `--trace 1` run leaves under
`bench/.cache/trace/<cell>/`, inside the harness's `bench.window` span:
  self time     of a span: its duration less what the program spans
                nested in it cover, on its thread;
  scope time    of a scope: the durations of device 0's operations
                whose op name (the `tf_op` stat of the op's event
                metadata) carries that scope as its innermost. The
                split is by kernel, not by scope: XLA fuses across
                scopes and names a fusion by its main op (on TPU its
                matmul, where it holds one), so a kernel counts whole
                under that op's scope, with whatever was fused into it;
  scopes held   by a kernel: the scopes of every instruction it runs,
                read from the HLO the profiler keeps for each module,
                so that the time of each mix of scopes shows what the
                scope time folds together;
  unattributed  the time in which device 0 is idle and no program span
                is open on any host thread;
  gaps by span  the ten longest idle gaps of device 0, found as
                `bench/trace.py` finds them, each named by the innermost
                program span that covers at least half of it, else
                "other".
A trace of a program without spans or scopes reads as empty, and every
metric that reads it then reports nothing.

    python3 -m bench.program_trace bench/.cache/trace/<cell> --steps N

from the repository's root prints the whole reduction of the newest
trace in that directory, per step of the N steps its window ran, with
the device time per step of each set of scopes that share a kernel.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import pathlib
import re
from typing import Callable, Dict, FrozenSet, List, Optional, Sequence, Tuple

from bench import trace as T

SPAN_PREFIX = "repro."
TRACE_ROOT = pathlib.Path(__file__).resolve().parent / ".cache" / "trace"
# a scope in an op name: "jit(step)/optim.update/mul",
# "jit(step)/jvp(gcn.xw)/dot_general", "transpose(jvp(gcn.xw))/..."
SCOPE = re.compile(r"(?:^|[/(])((?:gcn|optim|dp)\.[a-z_]+)(?=[/)\"]|$)")
# the stat of a device op's event metadata that holds its op name; a
# fusion's is the one XLA gives the fusion, that of its main op
OP_NAME_STAT = "tf_op"
# the profiler's metadata plane keeps each module's HLO, an xla.HloProto,
# as this stat of the module's event metadata
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
# an HLO instruction's name at the head of a device op's event name
INSTRUCTION = re.compile(r"%?([^\s=]+)")
# a traced window and the harness's own reduction of it agree to the ns
WINDOW_MATCH_S = 1e-6


@dataclasses.dataclass(frozen=True)
class Span:
    start: float                    # seconds on the profiler's clock
    end: float
    name: str                       # without SPAN_PREFIX
    stats: Tuple[Tuple[str, object], ...]
    thread: str

    def stat(self, key: str, default=None):
        return dict(self.stats).get(key, default)


Op = Tuple[float, float, Optional[str]]     # (start s, end s, scope)


@dataclasses.dataclass
class Program:
    window: Optional[Tuple[float, float]]   # the bench.window span
    spans: List[Span]
    ops: Dict[int, List[Op]]                # device id -> its operations


def scope_of(texts: Sequence[str]) -> Optional[str]:
    """The innermost scope named in the first text that names one."""
    for text in texts:
        found = SCOPE.findall(text)
        if found:
            return found[-1]
    return None


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes, start: int = 0, end: Optional[int] = None):
    """(field number, value) of a serialized protobuf message: a varint
    as an int, a length-delimited field as its (start, end) in `buf`."""
    i, end = start, len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind in (1, 5):
            value, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, value


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _messages(buf: bytes, span: Tuple[int, int], field: int):
    return [v for f, v in _fields(buf, *span) if f == field]


def _map_values(buf: bytes, span: Tuple[int, int], field: int) -> List:
    """The value spans of a protobuf map field (entries: key 1, value
    2)."""
    entries = [dict(_fields(buf, *e)) for e in _messages(buf, span, field)]
    return [(e.get(1), e[2]) for e in entries if 2 in e]


def op_names(path: str) -> Dict[str, str]:
    """Device op (its event name) -> its op name: the `tf_op` stat of its
    event metadata, which `ProfileEvent.stats` leaves out. Read from the
    serialized XSpace: its planes (field 1); a plane's name 2, event
    metadata 4 and stat metadata 5, maps of key 1 to value 2; an event
    metadata's name 2 and stats 5; a stat metadata's name 2; a stat's
    metadata id 1 and its string 5, or 7 for a stat metadata's name."""
    buf = pathlib.Path(path).read_bytes()
    out: Dict[str, str] = {}
    for plane in _messages(buf, (0, len(buf)), 1):
        name = dict(_fields(buf, *plane)).get(2, (0, 0))
        if not T.DEVICE_PLANE.match(_text(buf, name)):
            continue
        stat_names = {key: _text(buf, dict(_fields(buf, *v)).get(2, (0, 0)))
                      for key, v in _map_values(buf, plane, 5)}
        for _, meta in _map_values(buf, plane, 4):
            op = _text(buf, dict(_fields(buf, *meta)).get(2, (0, 0)))
            for st in _messages(buf, meta, 5):
                st = dict(_fields(buf, *st))
                if stat_names.get(st.get(1)) == OP_NAME_STAT:
                    out[op] = (_text(buf, st[5]) if 5 in st
                               else stat_names.get(st.get(7), ""))
    return out


def _packed(buf: bytes, value) -> List[int]:
    """A repeated varint field's values, packed or not."""
    if isinstance(value, int):
        return [value]
    out, i = [], value[0]
    while i < value[1]:
        v, i = _varint(buf, i)
        out.append(v)
    return out


def _module_scopes(buf: bytes, span: Tuple[int, int]
                   ) -> Dict[str, FrozenSet[str]]:
    """Instruction name -> scopes held, for one serialized HloProto: its
    module 1; a module's computations 3; a computation's instructions 2
    and id 5; an instruction's name 1, opcode 2, metadata 7 (whose op
    name is 2) and called computation ids 38."""
    computations: Dict[int, List[Tuple[str, str, Optional[str],
                                       List[int]]]] = {}
    for module in _messages(buf, span, 1):
        for comp in _messages(buf, module, 3):
            cid, instrs = 0, []
            for f, v in _fields(buf, *comp):
                if f == 5:
                    cid = v
                elif f == 2:
                    got = {1: (0, 0), 2: (0, 0), 7: None}
                    called: List[int] = []
                    for g, w in _fields(buf, *v):
                        if g == 38:
                            called += _packed(buf, w)
                        elif g in got and not isinstance(w, int):
                            got[g] = w
                    meta = dict(_fields(buf, *got[7])) if got[7] else {}
                    op_name = _text(buf, meta[2]) if 2 in meta else ""
                    instrs.append((_text(buf, got[1]), _text(buf, got[2]),
                                   scope_of([op_name]), called))
            computations[cid] = instrs
    memo: Dict[int, FrozenSet[str]] = {}

    def held(scope, opcode, called) -> FrozenSet[str]:
        out = {scope} if scope else set()
        if opcode == "fusion":
            for c in called:
                if c not in memo:
                    memo[c] = frozenset().union(*(
                        held(s, op, cs)
                        for _, op, s, cs in computations.get(c, [])))
                out |= memo[c]
        return frozenset(out)

    return {name: held(scope, opcode, called)
            for instrs in computations.values()
            for name, opcode, scope, called in instrs}


def kernel_scopes(path: str) -> Dict[str, FrozenSet[str]]:
    """HLO instruction name -> the scopes of every instruction it runs
    (a fusion's: those of its fused computations, nested ones too), from
    the HLO the profiler keeps in its metadata plane. The names of all
    the modules it kept are merged."""
    buf = pathlib.Path(path).read_bytes()
    out: Dict[str, FrozenSet[str]] = {}
    for plane in _messages(buf, (0, len(buf)), 1):
        name = dict(_fields(buf, *plane)).get(2, (0, 0))
        if _text(buf, name) != METADATA_PLANE:
            continue
        stat_names = {key: _text(buf, dict(_fields(buf, *v)).get(2, (0, 0)))
                      for key, v in _map_values(buf, plane, 5)}
        for _, meta in _map_values(buf, plane, 4):
            for st in _messages(buf, meta, 5):
                st = dict(_fields(buf, *st))
                if stat_names.get(st.get(1)) == HLO_STAT and 6 in st:
                    out.update(_module_scopes(buf, st[6]))
    return out


def held_label(held: Dict[str, FrozenSet[str]], event_name: str
               ) -> Optional[str]:
    """The scopes a device op's kernel holds, joined by "+"; None where
    it holds none or its instruction is not in `held`."""
    scopes = held.get(INSTRUCTION.match(event_name).group(1))
    return "+".join(sorted(scopes)) if scopes else None


def program_events(profile, names: Optional[Dict[str, str]] = None,
                   label: Optional[Callable[[str], Optional[str]]] = None
                   ) -> Program:
    """The program's host spans (with their stats and thread) and each
    device operation's scope, selected as `bench.trace.events` selects
    operations; and the harness's window. An operation's scope comes
    from its op name in `names` (`op_names`), else from its event name,
    the HLO text; or, where `label` is given, is `label(event name)`."""
    names = names or {}
    spans: List[Span] = []
    ops: Dict[int, List[Op]] = {}
    window = None
    scopes: Dict[str, Optional[str]] = {}     # op name -> its scope
    for plane in profile.planes:
        m = T.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (T.OPS_LINE, T.ASYNC_LINE):
                mine = ops.setdefault(int(m.group(1)), [])
                for e in line.events:
                    name = e.name
                    if line.name != T.OPS_LINE and \
                            not T.COLLECTIVE.search(name):
                        continue
                    if name not in scopes:
                        scopes[name] = (label(name) if label else scope_of(
                            [names.get(name, ""), name]))
                    s = e.start_ns * 1e-9
                    mine.append((s, (e.start_ns + e.duration_ns) * 1e-9,
                                 scopes[name]))
            elif not m:
                thread = f"{plane.name}/{line.name}"
                for e in line.events:
                    name = e.name
                    if name.startswith(SPAN_PREFIX):
                        spans.append(Span(
                            e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9,
                            name[len(SPAN_PREFIX):], tuple(e.stats),
                            thread))
                    elif name == T.WINDOW_SPAN and window is None:
                        window = (e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9)
    return Program(window=window, spans=spans, ops=ops)


def nest(spans: Sequence[Span]) -> Tuple[List[int], List[Tuple]]:
    """Each span's depth among the program spans of its thread, and the
    thread's time cut into (start, end, span index) self segments: each
    instant belongs to the innermost span open then. A child that
    outlives its parent is cut at the parent's end."""
    depth = [0] * len(spans)
    segments: List[Tuple[float, float, int]] = []
    threads: Dict[str, List[int]] = {}
    for i, sp in enumerate(spans):
        threads.setdefault(sp.thread, []).append(i)
    for idx in threads.values():
        idx.sort(key=lambda i: (spans[i].start, -spans[i].end))
        stack: List[Tuple[float, int]] = []     # (end, index)
        t = None
        for i in idx:
            s = spans[i].start
            while stack and stack[-1][0] <= s:
                end, j = stack.pop()
                segments.append((t, end, j))
                t = end
            end = spans[i].end
            if stack:
                segments.append((t, s, stack[-1][1]))
                end = min(end, stack[-1][0])
            depth[i] = len(stack)
            stack.append((end, i))
            t = s
        while stack:
            end, j = stack.pop()
            segments.append((t, end, j))
            t = end
    return depth, [x for x in segments if x[1] > x[0]]


def self_seconds(spans: Sequence[Span], lo: float, hi: float
                 ) -> Dict[str, float]:
    """Span name -> self time inside [lo, hi], summed over its spans."""
    _, segments = nest(spans)
    out: Dict[str, float] = {}
    for s, e, i in segments:
        c = min(e, hi) - max(s, lo)
        if c > 0:
            out[spans[i].name] = out.get(spans[i].name, 0.0) + c
    return out


def scope_seconds(ops: Sequence[Op], lo: float, hi: float
                  ) -> Dict[str, float]:
    """Scope -> seconds of the operations under it inside [lo, hi]."""
    out: Dict[str, float] = {}
    for s, e, scope in T.clip(ops, lo, hi):
        if scope is not None:
            out[scope] = out.get(scope, 0.0) + (e - s)
    return out


def idle(ops: Sequence[Op], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    return T.gaps(T.union(T.clip(ops, lo, hi)), lo, hi)


def unattributed_seconds(ops: Sequence[Op], spans: Sequence[Span],
                         lo: float, hi: float) -> float:
    """Idle device time with no program span open on any host thread."""
    host = T.union([(sp.start, sp.end) for sp in spans])
    return T.length(T.subtract(idle(ops, lo, hi), host))


def gaps_by_span(ops: Sequence[Op], spans: Sequence[Span], lo: float,
                 hi: float) -> List[Tuple[str, float]]:
    """The ten longest idle gaps, each named by the innermost span that
    covers at least half of it (the most-covering one at equal depth)."""
    depth, _ = nest(spans)
    longest = sorted(idle(ops, lo, hi), key=lambda g: g[0] - g[1])[:T.TOP]
    named = []
    for g0, g1 in longest:
        best, key = "other", None
        for i, sp in enumerate(spans):
            cover = min(sp.end, g1) - max(sp.start, g0)
            if cover >= 0.5 * (g1 - g0) and (key is None or
                                              (depth[i], cover) > key):
                best, key = sp.name, (depth[i], cover)
        named.append((best, g1 - g0))
    return named


@dataclasses.dataclass
class ProgramReduced:
    window_s: float
    steps: int                          # the harness's window steps
    span_s: Dict[str, float]            # span name -> self seconds
    scope_s: Dict[str, float]           # scope -> device seconds
    epoch_ends: List[Span]              # engine.epoch_end in the window
    unattributed_s: Optional[float]     # None: the program has no span
    idle_gaps_by_span: List[Tuple[str, float]]

    def span_ms_per_step(self, name: str) -> Optional[float]:
        if name not in self.span_s or not self.steps:
            return None
        return 1e3 * self.span_s[name] / self.steps

    def scope_ms_per_step(self, *scopes: str) -> Optional[float]:
        found = [self.scope_s[s] for s in scopes if s in self.scope_s]
        if not found or not self.steps:
            return None
        return 1e3 * sum(found) / self.steps

    def epoch_end_ms_per_step(self) -> Optional[float]:
        """Read-back milliseconds per step of the epochs it read."""
        steps = sum(sp.stat("steps", 0) for sp in self.epoch_ends)
        if not steps:
            return None
        return 1e3 * sum(sp.end - sp.start
                         for sp in self.epoch_ends) / steps

    def syncs_per_step(self) -> Optional[float]:
        steps = sum(sp.stat("steps", 0) for sp in self.epoch_ends)
        if not steps:
            return None
        return sum(sp.stat("syncs", 0) for sp in self.epoch_ends) / steps

    def unattributed_pct(self) -> Optional[float]:
        if self.unattributed_s is None:
            return None
        return 100.0 * self.unattributed_s / self.window_s

    def breakdown(self) -> Dict:
        return {"span_ms_per_step": {k: self.span_ms_per_step(k)
                                     for k in sorted(self.span_s)},
                "scope_ms_per_step": {k: self.scope_ms_per_step(k)
                                      for k in sorted(self.scope_s)},
                "epoch_end_ms_per_step": self.epoch_end_ms_per_step(),
                "host_syncs_per_step": self.syncs_per_step(),
                "idle_unattributed_pct": self.unattributed_pct(),
                "idle_gaps_by_span": [list(x)
                                      for x in self.idle_gaps_by_span]}


def _extent(program: Program, device_ids: Sequence[int]
            ) -> Tuple[float, float]:
    every = [(s, e) for d in device_ids for s, e, _ in program.ops.get(d, [])]
    every += [(sp.start, sp.end) for sp in program.spans]
    return min(s for s, _ in every), max(e for _, e in every)


def reduce_program(program: Program, device_ids: Sequence[int],
                   steps: int) -> ProgramReduced:
    """The window is the harness's `bench.window` span, or where there
    is none, the extent of the chips' operations and the spans."""
    lo, hi = program.window or _extent(program, device_ids)
    first = program.ops.get(device_ids[0], []) if device_ids else []
    spans = program.spans
    return ProgramReduced(
        window_s=hi - lo, steps=steps, span_s=self_seconds(spans, lo, hi),
        scope_s=scope_seconds(first, lo, hi),
        epoch_ends=[sp for sp in spans if sp.name == "engine.epoch_end"
                    and sp.start >= lo and sp.end <= hi],
        unattributed_s=(unattributed_seconds(first, spans, lo, hi)
                        if spans else None),
        idle_gaps_by_span=gaps_by_span(first, spans, lo, hi))


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, mtime_ns: int, chips: int, steps: int
                 ) -> ProgramReduced:
    import jax
    program = program_events(jax.profiler.ProfileData.from_file(path),
                             op_names(path))
    return reduce_program(program, sorted(program.ops)[:chips], steps)


def newest_trace(directory: pathlib.Path) -> Optional[pathlib.Path]:
    files = sorted(pathlib.Path(directory).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime_ns)
    return files[-1] if files else None


def read(run, root: Optional[pathlib.Path] = None
         ) -> Optional[ProgramReduced]:
    """The program's reduction of the trace this run left: the newest
    under `root` (default `TRACE_ROOT`), if its window is the one
    `run.trace` reduced."""
    if run.trace is None:
        return None
    path = newest_trace(TRACE_ROOT if root is None else root)
    if path is None:
        return None
    got = _reduce_file(str(path), path.stat().st_mtime_ns, run.chips,
                       run.steps)
    if abs(got.window_s - run.trace.window_s) > WINDOW_MATCH_S:
        return None
    return got


def main(argv=None) -> int:
    import jax
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("directory", type=pathlib.Path)
    ap.add_argument("--steps", type=int, default=None,
                    help="window steps to divide by (default: the "
                    "engine.step spans that start in the window)")
    ap.add_argument("--chips", type=int, default=1)
    args = ap.parse_args(argv)
    path = newest_trace(args.directory)
    if path is None:
        print(f"no profiler trace under {args.directory}")
        return 1
    profile = jax.profiler.ProfileData.from_file(str(path))
    program = program_events(profile, op_names(str(path)))
    device_ids = sorted(program.ops)[:args.chips]
    steps = args.steps
    if steps is None:
        lo, hi = program.window or _extent(program, device_ids)
        steps = sum(sp.name == "engine.step" and lo <= sp.start < hi
                    for sp in program.spans)
    got = reduce_program(program, device_ids, steps)
    out = {"trace": str(path), "window_s": got.window_s, "steps": steps,
           **got.breakdown()}
    held = kernel_scopes(str(path))
    if held and device_ids and steps:
        lo, hi = program.window or _extent(program, device_ids)
        kernels = program_events(profile,
                                 label=functools.partial(held_label, held))
        out["kernel_scopes_ms_per_step"] = {
            k: 1e3 * v / steps for k, v in sorted(scope_seconds(
                kernels.ops.get(device_ids[0], []), lo, hi).items())}
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
