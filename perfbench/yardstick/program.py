"""The training loop's own spans in a profiler trace, and the device's idle
time split by the loop phase the host was in.

`load` reads the host spans that `launch.train.Trainer` puts into the
`.xplane.pb` that `trace.load` reads, as one more key of a trace record:

    {"program": [[span name, start_ns, duration_ns, step_num], ...]}

with every host event whose name starts with `train.`; `step_num` is that
of each `train.step`, and None on the phases inside it.

The functions below read a record that has this key beside `devices`, and
give None where it lacks it or holds no span (a trace of a program without
the spans), so a small recorded one (`tests/bench/data/`) checks them
without a chip.
"""
from __future__ import annotations

import glob
from pathlib import Path
from typing import Dict, List, Optional

from yardstick import trace

SPAN_PREFIX = "train."
STEP_SPAN = "train.step"
WAIT_SPAN = "train.wait"
UNTRACED = "untraced"


def load(trace_dir: str) -> dict:
    import jax

    paths = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    program: list = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    step = (dict(e.stats).get("step_num")
                            if e.name == STEP_SPAN else None)
                    program.append([e.name, e.start_ns, e.duration_ns,
                                    None if step is None else int(step)])
    return {"program": sorted(program, key=lambda s: (s[1], -s[2]))}


def _labelled(program: list, lo: int, hi: int) -> List[list]:
    """[start, end, innermost span] pieces tiling [lo, hi); `untraced`
    where no span is open."""
    cuts = sorted({lo, hi} | {t for _n, s, d, _k in program
                              for t in (s, s + d) if lo < t < hi})
    spans = sorted((s, s + d, n) for n, s, d, _k in program
                   if s < hi and s + d > lo)
    out, active, i = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(spans) and spans[i][0] <= a:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[1] > a]
        name = (min(active, key=lambda sp: sp[1] - sp[0])[2] if active
                else UNTRACED)
        out.append([a, b, name])
    return out


def idle_intervals(rec: dict, plane: str, lo: int, hi: int) -> List[tuple]:
    """The intervals of [lo, hi) in which no operation ran on a chip."""
    busy = trace.union([iv for _, iv in trace._ops(rec, plane, lo, hi)])
    idle, cur = [], lo
    for s, e in busy:
        if s > cur:
            idle.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        idle.append((cur, hi))
    return idle


def phase_idle(rec: dict, lo: int, hi: int
               ) -> Optional[Dict[str, Dict[str, int]]]:
    """Per chip: ns of the window in which no operation ran, split by the
    innermost `train.*` span around each part of each idle interval
    (`untraced` where none is).  None without program spans."""
    program = rec.get("program")
    if not program:
        return None
    pieces = _labelled(program, lo, hi)
    out = {}
    for plane in trace.sorted_planes(rec):
        idle = idle_intervals(rec, plane, lo, hi)
        by: Dict[str, int] = {}
        j = 0
        for a, b, name in pieces:
            while j < len(idle) and idle[j][1] <= a:
                j += 1
            k = j
            while k < len(idle) and idle[k][0] < b:
                by[name] = by.get(name, 0) + min(b, idle[k][1]) - max(a, idle[k][0])
                k += 1
        out[plane] = by
    return out


def loop_gap_ms(rec: dict, lo: int, hi: int, n_steps: int) -> Optional[float]:
    """Device-idle ms per step outside `train.wait` (the chip waiting on
    the loop's host work), mean over chips."""
    idle = phase_idle(rec, lo, hi)
    if not idle:
        return None
    tot = sum(sum(ns for name, ns in by.items() if name != WAIT_SPAN)
              for by in idle.values())
    return tot / len(idle) / n_steps * 1e-6
