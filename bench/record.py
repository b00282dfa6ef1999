"""What one run of a cell recorded: the input of every metric reader."""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional


@dataclasses.dataclass
class Run:
    chips: int
    peaks: Dict                       # the device's row of peaks.json
    setup_s: float                    # process start to the first timed step
    setup_parts: Dict[str, float]     # host-clock phases of the set-up
    window_compile_s: float           # host seconds compiling in the window
    window_s: float                   # host clock, first timed step to sync
    steps: int                        # steps completed in the window
    nodes: int                        # real nodes those steps trained on
    attempted: int
    failed: int                       # window steps with a non-finite loss
    build_s: List[float]              # host batch builds in the window
    payload_bytes: List[int]          # host payload bytes of window steps
    memory_peak_bytes: Optional[int]
    trace: Optional[object] = None    # bench.trace.Reduced, --trace 1 only
    # () -> operations the window's steps require (bench.flops)
    required_flops: Optional[Callable[[], float]] = None
    numbers: Dict[str, float] = dataclasses.field(default_factory=dict)
