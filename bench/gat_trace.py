"""The graph attention layer's device scopes (`gat.xw`, `gat.attention`,
`gat.aggregate`) in the trace a `--trace 1` run leaves, reduced as
`bench.program_trace` reduces the program's other scopes: a kernel
counts whole under the innermost scope of the op that leads it. A trace
of a program without these scopes reads as having none, and the metrics
that read them then report nothing.
"""
from __future__ import annotations

import functools
import pathlib
import re
from typing import Optional

from bench import program_trace

# the program's scopes, the attention layer's among them; a kernel is
# the attention's only where the innermost of its op's scopes is
SCOPE = re.compile(
    r"(?:^|[/(])((?:gat|gcn|optim|dp)\.[a-z_]+)(?=[/)\"]|$)")


def scope_of(*texts: str) -> Optional[str]:
    """The innermost `gat.*` scope named in the first text that names a
    scope of the program, else None."""
    for text in texts:
        found = SCOPE.findall(text)
        if found:
            return found[-1] if found[-1].startswith("gat.") else None
    return None


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, mtime_ns: int, chips: int, steps: int
                 ) -> program_trace.ProgramReduced:
    import jax
    names = program_trace.op_names(path)
    program = program_trace.program_events(
        jax.profiler.ProfileData.from_file(path),
        label=lambda name: scope_of(names.get(name, ""), name))
    return program_trace.reduce_program(program, sorted(program.ops)[:chips],
                                        steps)


def read(run, root: Optional[pathlib.Path] = None
         ) -> Optional[program_trace.ProgramReduced]:
    """The reduction of the trace this run left, by `gat.*` scope: the
    newest trace under `root` (default `program_trace.TRACE_ROOT`), if
    its window is the one `run.trace` reduced."""
    if run.trace is None:
        return None
    path = program_trace.newest_trace(
        program_trace.TRACE_ROOT if root is None else root)
    if path is None:
        return None
    got = _reduce_file(str(path), path.stat().st_mtime_ns, run.chips,
                       run.steps)
    if abs(got.window_s - run.trace.window_s) > program_trace.WINDOW_MATCH_S:
        return None
    return got
