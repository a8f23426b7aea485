"""Mesh/torus topology model: replica groups -> mesh axes -> link classes.

This is the `UCT transport` resolution layer: where ucTrace maps a UCT send
to (rc_mlx5 | cuda_ipc | sysv | gdr_copy) + a NIC, we map an HLO collective's
replica groups onto the device mesh and classify which interconnect the
traffic rides: intra-pod ICI torus axes vs the inter-pod DCI.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import lru_cache
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


@dataclass(frozen=True)
class Hardware:
    """Per-chip peaks and per-link constants of one TPU generation."""

    name: str
    flops_bf16: float                   # peak bf16 FLOP/s per chip
    hbm_bw: float                       # HBM bytes/s per chip
    ici_bw: float                       # bytes/s per ICI link (per direction)
    dci_bw: float                       # bytes/s per inter-pod link
    ici_latency_s: float                # per-hop collective latency
    dci_latency_s: float
    hbm_per_chip: float                 # HBM capacity, bytes
    vmem_per_core: float                # VMEM bytes
    # eager/rendezvous analogue: below this payload a transfer is
    # latency-dominated ("eager"), above it bandwidth-dominated ("rndv").
    rndv_threshold: int = 1 << 16


# Peaks from Google Cloud's "TPU v5e" documentation: 197 TFLOP/s bf16,
# 16 GB HBM at 819 GB/s, 1,600 Gbit/s of ICI per chip (4 links, so
# 50 GB/s each way per link).  The latencies, the DCI link and the
# rendezvous threshold are modelling assumptions, not measurements.
V5E = Hardware(name="TPU v5 lite", flops_bf16=197e12, hbm_bw=819e9,
               ici_bw=50e9, dci_bw=25e9, ici_latency_s=1e-6,
               dci_latency_s=10e-6, hbm_per_chip=16e9,
               vmem_per_core=128 * 2**20)

# `device_kind` as jax reports it -> that chip's Hardware.  Offline
# analysis of HLO text prices against V5E; the chip path looks its device
# up here, and a device not in the table is an error, not a default.
PEAKS: Dict[str, Hardware] = {"TPU v5 lite": V5E}


def hardware_for(device_kind: str) -> Hardware:
    """The `Hardware` of a device kind; raises for a kind not in PEAKS."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks for device_kind {device_kind!r}: add it to "
            f"topology.PEAKS with its published source") from None


@dataclass(frozen=True)
class MeshSpec:
    """Logical device mesh + interconnect class per axis."""

    shape: Tuple[int, ...]
    axes: Tuple[str, ...]
    # axis name -> "ici" | "dci"
    axis_kind: Dict[str, str] = field(default_factory=dict)

    def __post_init__(self):
        assert len(self.shape) == len(self.axes)
        if not self.axis_kind:
            object.__setattr__(
                self, "axis_kind",
                {a: ("dci" if a == "pod" else "ici") for a in self.axes})

    @property
    def num_devices(self) -> int:
        return int(np.prod(self.shape))

    def coords(self, device_id: int) -> Tuple[int, ...]:
        return tuple(int(c) for c in np.unravel_index(device_id, self.shape))

    def coords_array(self, device_ids: Sequence[int]) -> np.ndarray:
        return np.stack(np.unravel_index(np.asarray(device_ids), self.shape),
                        axis=-1)

    @classmethod
    def single_pod(cls) -> "MeshSpec":
        return cls((16, 16), ("data", "model"))

    @classmethod
    def multi_pod(cls) -> "MeshSpec":
        return cls((2, 16, 16), ("pod", "data", "model"))


def varying_axes(mesh: MeshSpec, group: Sequence[int]) -> Tuple[str, ...]:
    """Which mesh axes vary across the devices of one replica group."""
    if len(group) <= 1:
        return ()
    coords = mesh.coords_array(group)
    out = []
    for i, name in enumerate(mesh.axes):
        if len(np.unique(coords[:, i])) > 1:
            out.append(name)
    return tuple(out)


def link_class(mesh: MeshSpec, axes: Tuple[str, ...]) -> str:
    """Transport-class label for a collective spanning `axes`."""
    if not axes:
        return "local"
    if len(axes) == 1:
        a = axes[0]
        return f"{mesh.axis_kind[a]}.{a}"
    kinds = {mesh.axis_kind[a] for a in axes}
    label = "+".join(axes)
    if kinds == {"ici"}:
        return f"ici.mixed({label})"
    if kinds == {"dci"}:
        return f"dci.mixed({label})"
    return f"xpod.mixed({label})"  # crosses both ICI and DCI


def slowest_link_bw(mesh: MeshSpec, axes: Tuple[str, ...], hw: Hardware) -> float:
    """Bottleneck link bandwidth for traffic spanning `axes`."""
    if not axes:
        return hw.hbm_bw
    bws = [hw.dci_bw if mesh.axis_kind[a] == "dci" else hw.ici_bw for a in axes]
    return min(bws)


def hop_latency(mesh: MeshSpec, axes: Tuple[str, ...], hw: Hardware) -> float:
    if not axes:
        return 0.0
    return max(hw.dci_latency_s if mesh.axis_kind[a] == "dci" else hw.ici_latency_s
               for a in axes)


@lru_cache(maxsize=4096)
def _resolve_iota_cached(num_groups: int, group_size: int,
                         reshape_dims: Tuple[int, ...],
                         transpose_perm: Optional[Tuple[int, ...]]
                         ) -> Tuple[Tuple[int, ...], ...]:
    n = int(np.prod(reshape_dims))
    ids = np.arange(n).reshape(reshape_dims)
    if transpose_perm is not None:
        ids = ids.transpose(transpose_perm)
    ids = ids.reshape(num_groups, group_size)
    return tuple(tuple(map(int, row)) for row in ids)


def resolve_iota_groups(num_groups: int, group_size: int,
                        reshape_dims: Sequence[int],
                        transpose_perm: Optional[Sequence[int]]) -> List[List[int]]:
    """Decode HLO iota replica groups `[G,S]<=[dims]T(perm)`.

    Memoized on the raw attribute tuple: unrolled loops stamp the same
    `replica_groups=[G,S]<=[dims]` attr onto thousands of ops, so the
    numpy decode runs once per unique attr; only the (cheap) list
    materialization happens per call, keeping results mutation-safe.

    Raises `ValueError` on a malformed attr (G*S != prod(dims), or a
    transpose perm that is not a permutation of the dims) instead of an
    opaque numpy reshape/transpose error — parser callers catch it and
    fall back to a full-range group.
    """
    dims = tuple(int(d) for d in reshape_dims)
    n = int(np.prod(dims)) if dims else 0
    if int(num_groups) * int(group_size) != n:
        raise ValueError(
            f"iota replica_groups [{num_groups},{group_size}]<={list(dims)}: "
            f"{num_groups}*{group_size} != prod(dims) = {n}")
    if transpose_perm is not None \
            and sorted(int(p) for p in transpose_perm) != list(range(len(dims))):
        raise ValueError(
            f"iota replica_groups transpose T({list(transpose_perm)}) is not "
            f"a permutation of {len(dims)} dims")
    rows = _resolve_iota_cached(
        int(num_groups), int(group_size), tuple(int(d) for d in reshape_dims),
        None if transpose_perm is None else tuple(int(p) for p in transpose_perm))
    return [list(r) for r in rows]


def comm_matrix(mesh: MeshSpec, events, resolution: str = "device") -> np.ndarray:
    """Device x device wire-byte matrix (ring-model neighbor traffic).

    The paper's Fig 3b analogue.  Ring collectives put traffic on ring
    neighbors within each replica group; permutes follow their explicit
    source->target pairs.

    `events` may be a `Trace`, a `TraceStore`, or a plain event iterable.
    The first two scatter a precomputed (src, dst, bytes) edge list with
    one `np.add.at` call instead of walking Python objects.
    """
    n = mesh.num_devices
    mat = np.zeros((n, n))
    store = getattr(events, "store", None)     # Trace -> its columnar store
    if store is None and hasattr(events, "ring_edges"):
        store = events                         # already a TraceStore
    if store is not None:
        src, dst, w = store.ring_edges()
        np.add.at(mat, (src, dst), w)
        return mat
    for e in events:
        mult = e.multiplicity
        if e.source_target_pairs:
            per = e.operand_bytes
            for s, t in e.source_target_pairs:
                mat[s, t] += per * mult
            continue
        for group in e.replica_groups:
            g = len(group)
            if g <= 1:
                continue
            per_link = e.wire_bytes_per_device * mult
            for i, d in enumerate(group):
                nxt = group[(i + 1) % g]
                mat[d, nxt] += per_link
    return mat


def reduce_matrix(mat: np.ndarray, mesh: MeshSpec, axis: str) -> np.ndarray:
    """Aggregate the device matrix to groups along one axis (viz)."""
    ai = mesh.axes.index(axis)
    k = mesh.shape[ai]
    n = mat.shape[0]
    labels = np.unravel_index(np.arange(n), mesh.shape)[ai]
    out = np.zeros((k, k))
    np.add.at(out, (labels[:, None], labels[None, :]), mat)
    return out
