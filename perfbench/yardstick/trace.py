"""From a profiler trace to device busy time, idle gaps and top
operations.

`load` reads the `.xplane.pb` the JAX profiler wrote into a plain record:

    {"devices": {plane name: [[op name, start_ns, duration_ns], ...]},
     "spans":   [[span name, start_ns, duration_ns], ...]}

with the device operations of each chip (the "XLA Ops" line of each
`/device:TPU:n` plane) and the benchmark's own host spans (`bench.*`).
A v5e trace names each operation by its HLO instruction only; the
driver adds what the executable's HLO text says of each (`scope_map`):

    {"scopes": {op name: op_name path of its metadata}}

Every reduction below reads that record only, so a small recorded one
(`tests/bench/data/`) checks them without a chip.
"""
from __future__ import annotations

import glob
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

OPS_LINE = "XLA Ops"
# the trace rounds picoseconds to ns: a body op may end 1 ns after its loop
NEST_SLACK_NS = 10
SPAN_PREFIX = "bench."

# the program's named scopes, innermost first: an op counts for the first
# of these that is a whole component of its op-name path
SCOPES = ("attn", "embed", "logits", "loss", "optimizer", "mlp", "layer")
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+) = .*?metadata=\{[^}]*?'
                    r'op_name="((?:[^"\\]|\\.)*)"', re.M)
_WRAPPED = re.compile(r"\w+\((.*)\)")

Interval = Tuple[int, int]


def load(trace_dir: str) -> dict:
    import jax

    paths = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    devices: Dict[str, list] = {}
    spans: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [[short(e.name), e.start_ns,
                                            e.duration_ns] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [[e.name, e.start_ns, e.duration_ns]
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "spans": spans}


def short(name: str) -> str:
    """`%fusion.12 = f32[...] fusion(...)` -> `fusion.12`."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def measure(intervals: List[Interval]) -> int:
    return sum(e - s for s, e in union(intervals))


def window(rec: dict, name: str = "bench.window") -> Interval:
    hits = [(s, s + d) for n, s, d in rec["spans"] if n == name]
    if len(hits) != 1:
        raise ValueError(f"{len(hits)} spans named {name!r} in the trace")
    return hits[0]


def _ops(rec: dict, plane: str, lo: int, hi: int):
    out = []
    for n, s, d in rec["devices"][plane]:
        out += [(n, iv) for iv in clip([(s, s + d)], lo, hi)]
    return out


def self_times(ops) -> List[list]:
    """[name, (start, end), self ns, has children] of each op.  The ops
    line nests (a `while` spans its body's ops), so an op's own time is
    its length less that of the ops wholly inside it; ops that only
    overlap are siblings."""
    out: List[list] = []
    stack: List[list] = []
    for name, (s, e) in sorted(ops, key=lambda o: (o[1][0], -o[1][1])):
        while stack and stack[-1][1][1] + NEST_SLACK_NS < e:  # ended or overlaps
            stack.pop()
        row = [name, (s, e), e - s, False]
        if stack:
            parent = stack[-1]
            parent[2] -= min(e, parent[1][1]) - s
            parent[3] = True
        stack.append(row)
        out.append(row)
    return out


def sorted_planes(rec: dict) -> List[str]:
    return sorted(rec["devices"])


def busy_ns(rec: dict, lo: int, hi: int) -> Dict[str, int]:
    """Per chip: ns of the window in which some operation ran."""
    return {p: measure([iv for _, iv in _ops(rec, p, lo, hi)])
            for p in sorted_planes(rec)}


def top_ops(rec: dict, lo: int, hi: int, n: int = 10) -> List[list]:
    """[op name, seconds] of the n operations with the most own time (a
    loop's body ops count for themselves, not for the loop), mean over
    chips."""
    tot: Dict[str, float] = {}
    planes = sorted_planes(rec)
    for p in planes:
        for name, _iv, own, _kids in self_times(_ops(rec, p, lo, hi)):
            tot[name] = tot.get(name, 0.0) + own * 1e-9 / len(planes)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(rec: dict, lo: int, hi: int, n: int = 10) -> List[list]:
    """[span, seconds] of the n longest idle gaps of the first chip, each
    named by the innermost benchmark span around its midpoint."""
    plane = sorted_planes(rec)[0]
    busy = union([iv for _, iv in _ops(rec, plane, lo, hi)])
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    spans = [(nm, s, s + d) for nm, s, d in rec["spans"]]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) // 2
        inside = [(e2 - s2, nm) for nm, s2, e2 in spans if s2 <= mid < e2]
        out.append([min(inside)[1] if inside else "untraced", (e - s) * 1e-9])
    return out



def scope_map(hlo_text: str) -> Dict[str, str]:
    """{instruction name: op_name path} of every instruction of an HLO
    module's text that carries one."""
    return {m.group(1): m.group(2) for m in _INSTR.finditer(hlo_text)}


def scope_of(path: str) -> Optional[str]:
    """The scope of an op-name path: its components with transform
    wrappers stripped (`transpose(jvp(loss))` -> `loss`), then the first
    of `SCOPES` that is one of them, whole.  None where none is."""
    parts = set()
    for c in path.split("/"):
        while (m := _WRAPPED.fullmatch(c)):
            c = m.group(1)
        parts.add(c)
    return next((s for s in SCOPES if s in parts), None)


def scope_ms(rec: dict, lo: int, hi: int, n_steps: int,
             names: Iterable[str]) -> Optional[float]:
    """Own device ms per step of the ops whose scope is one of `names`,
    mean over chips.  None without a scope map, or where no such op ran."""
    scopes = rec.get("scopes")
    if not scopes:
        return None
    names = set(names)
    of = {}
    planes = sorted_planes(rec)
    tot, hit = 0, False
    for p in planes:
        for name, _iv, own, _kids in self_times(_ops(rec, p, lo, hi)):
            if name not in of:
                of[name] = scope_of(scopes.get(name, "")) in names
            if of[name]:
                tot += own
                hit = True
    return tot / len(planes) / n_steps * 1e-6 if hit else None
