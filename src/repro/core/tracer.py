"""Tracer orchestration: compile (or accept compiled) -> assemble a Trace.

Pipeline (the paper's Fig 2, compile-time edition):
  (1) lower + partition the step           (jit .lower().compile())
  (2) parse collectives out of the HLO     (hlo_parser  — "recording UCT")
  (3) resolve groups onto the mesh         (topology    — transport/NIC attribution)
  (4) model completions                    (costmodel   — completion tracking)
  (5) attribute scopes/semantics           (attribution — UCP/MPI attribution)
  (6) aggregate + render                   (report      — log processing + viz)
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

from repro.core import attribution, costmodel, hlo_parser
from repro.core.events import Trace
from repro.core.topology import Hardware, MeshSpec, V5E, hardware_for


def trace_from_hlo(hlo_text: str, mesh: MeshSpec, *, label: str = "step",
                   hw: Hardware = V5E,
                   cost_analysis: Optional[Dict[str, float]] = None,
                   memory_analysis: Any = None,
                   engine: str = "columnar",
                   shards: Optional[int] = None,
                   shard_workers: Optional[int] = None,
                   recover: bool = False) -> Trace:
    """Assemble a multi-layer trace from compiled HLO text.

    HLO text does not name its device, so it is priced on `hw` (v5e
    unless told otherwise); `trace_compiled`/`trace_step` look the
    executable's own device up in `topology.PEAKS` instead.

    `engine` selects the ingest pipeline:
      * `"columnar"` (default) — single-pass parse straight into
        `TraceStore` columns, batched cost model + vocab-level attribution
        (`annotate_store` / `attribute_store`); event rows stay lazy.
      * `"rows"` — the per-event reference path (dataclass per site,
        `annotate_event` / `attribute_event` per event).  Kept as the
        equivalence baseline; see tests/test_ingest.py.

    `shards` (columnar only) splits one giant module per-computation
    across worker processes (`hlo_parser.parse_hlo_store_sharded`), with
    the shard stores merged back byte-identically to a serial parse.
    `None` auto-shards above `hlo_parser.AUTO_SHARD_BYTES`; `1` forces
    the serial path.  `shard_workers` caps the pool (0 = in-process).

    `recover=True` (columnar only) ingests a damaged module through
    salvage parsing (`parse_hlo_store(recover=True)`): instead of
    raising on truncated/corrupted input, the intact computations are
    kept and `trace.salvage` carries the `SalvageReport` of what was
    dropped.  Salvage always parses serially — a damaged module must
    not be sharded across workers on unverified boundaries.
    """
    salvage = None
    if engine == "columnar":
        n_shards = shards if shards is not None \
            else hlo_parser.auto_shards(len(hlo_text))
        if recover:
            store, stats, salvage = hlo_parser.parse_hlo_store(
                hlo_text, mesh.num_devices, recover=True)
        elif n_shards > 1:
            store, stats = hlo_parser.parse_hlo_store_sharded(
                hlo_text, mesh.num_devices, n_shards,
                max_workers=shard_workers)
        else:
            store, stats = hlo_parser.parse_hlo_store(
                hlo_text, mesh.num_devices)
        costmodel.annotate_store(store, mesh, hw)
        attribution.attribute_store(store)
        tr = Trace.from_store(label, mesh.shape, mesh.axes, mesh.num_devices,
                              store, op_stats=stats)
        tr.salvage = salvage
    elif engine == "rows":
        events, stats = hlo_parser.parse_hlo(hlo_text, mesh.num_devices)
        for ev in events:
            costmodel.annotate_event(ev, mesh, hw)
        attribution.attribute_all(events)
        tr = Trace(label=label, mesh_shape=mesh.shape, mesh_axes=mesh.axes,
                   num_devices=mesh.num_devices, events=events, op_stats=stats)
    else:
        raise ValueError(f"unknown ingest engine: {engine!r}")
    # loop-aware parsed totals are authoritative (cost_analysis counts while
    # bodies once); fall back to cost_analysis when parsing finds nothing.
    tr.hlo_flops = float(stats.flops)
    tr.hlo_bytes = float(stats.bytes_accessed)
    if cost_analysis:
        ca_flops = float(cost_analysis.get("flops", 0.0))
        ca_bytes = float(cost_analysis.get("bytes accessed", 0.0))
        tr.hlo_flops = max(tr.hlo_flops, ca_flops)
        tr.hlo_bytes = max(tr.hlo_bytes, ca_bytes)
    if memory_analysis is not None:
        tr.per_device_memory_bytes = float(
            getattr(memory_analysis, "temp_size_in_bytes", 0)
            + getattr(memory_analysis, "argument_size_in_bytes", 0)
            + getattr(memory_analysis, "output_size_in_bytes", 0)
            - getattr(memory_analysis, "alias_size_in_bytes", 0))
        tr.argument_bytes = float(
            getattr(memory_analysis, "argument_size_in_bytes", 0))
        tr.output_bytes = float(
            getattr(memory_analysis, "output_size_in_bytes", 0))
    return tr


@dataclass
class TraceResult:
    trace: Trace
    compiled: Any
    lowered: Any
    lower_s: float
    compile_s: float
    parse_s: float
    hlo_chars: int


def compiled_hardware(compiled) -> Hardware:
    """The `Hardware` of the devices a jax `Compiled` runs on.

    Raises for a device kind that `topology.PEAKS` does not list.
    """
    import jax

    shardings = jax.tree.leaves((compiled.input_shardings,
                                 compiled.output_shardings))
    kinds = {d.device_kind for sh in shardings for d in sh.device_set}
    if len(kinds) != 1:
        raise ValueError(f"executable spans device kinds {sorted(kinds)}")
    return hardware_for(kinds.pop())


def trace_compiled(compiled, mesh: MeshSpec, *, label: str = "step",
                   hw: Optional[Hardware] = None, engine: str = "columnar",
                   shards: Optional[int] = None) -> Trace:
    """Trace an already-compiled step (jax Compiled object).

    `hw=None` prices on the executable's own device (`compiled_hardware`).
    """
    return trace_from_hlo(compiled.as_text(), mesh, label=label,
                          hw=hw or compiled_hardware(compiled),
                          cost_analysis=compiled.cost_analysis(),
                          memory_analysis=compiled.memory_analysis(),
                          engine=engine, shards=shards)


def trace_step(fn: Callable, args_specs, mesh_jax, mesh_spec: MeshSpec, *,
               in_shardings=None, out_shardings=None, label="step",
               hw: Optional[Hardware] = None,
               donate_argnums=()) -> TraceResult:
    """Lower + compile `fn` on `mesh_jax` and assemble the trace.

    `hw=None` prices on the compiled step's own device.
    """
    import jax

    t0 = time.perf_counter()
    jfn = jax.jit(fn, in_shardings=in_shardings, out_shardings=out_shardings,
                  donate_argnums=donate_argnums)
    with mesh_jax:
        lowered = jfn.lower(*args_specs)
        t1 = time.perf_counter()
        compiled = lowered.compile()
    t2 = time.perf_counter()
    tr = trace_compiled(compiled, mesh_spec, label=label, hw=hw)
    t3 = time.perf_counter()
    return TraceResult(trace=tr, compiled=compiled, lowered=lowered,
                       lower_s=t1 - t0, compile_s=t2 - t1, parse_s=t3 - t2,
                       hlo_chars=len(compiled.as_text()))
