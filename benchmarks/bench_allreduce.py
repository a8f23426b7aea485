"""Fig 5 analogue: Allreduce algorithm comparison (ring / RSAG / recursive
doubling / XLA builtin) — traced signatures + modeled v5e times at 256 chips.

The paper contrasts Open MPI vs MPICH algorithm choices through their
communication graphs; here each algorithm is built explicitly from
shard_map+ppermute so the tracer shows its distinct wire pattern.
"""
from __future__ import annotations

import json

from _util import run_worker

WORKER = """
import json
import time
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.core import MeshSpec, trace_from_hlo
from repro.core.costmodel import allreduce_time
from repro.core.topology import V5E
from repro.distributed.algorithms import ALGORITHMS, allreduce_fn
from repro.launch.mesh import make_mesh

mesh = make_mesh((8,), ("data",))
spec = MeshSpec((8,), ("data",))
NB = 1 << 22          # 4 MiB payload
x = jnp.ones((8, NB // 4 // 8), jnp.float32)
xd = jax.device_put(x, NamedSharding(mesh, P("data")))

rows = []
for name in ALGORITHMS:
    fn = jax.jit(allreduce_fn(name, mesh))
    compiled = fn.lower(xd).compile()
    for _ in range(2):
        out = fn(xd)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(5):
        out = fn(xd)
    jax.block_until_ready(out)
    us = (time.perf_counter() - t0) / 5 * 1e6
    tr = trace_from_hlo(compiled.as_text(), spec, label=name)
    wire = tr.total_wire_bytes()
    n_ev = sum(e.multiplicity for e in tr.events)
    kinds = sorted({e.kind for e in tr.events})
    model_us = tr.total_est_time_s() * 1e6
    rows.append((f"allreduce/{name}/4MiB", us,
                 f"events={n_ev}|kinds={'+'.join(kinds)}|"
                 f"wireMB={wire/1e6:.1f}|v5e={model_us:.1f}us"))

# closed-form comparison at production scale (256 chips, 100 MB gradient)
for name in ("ring", "reduce_scatter_allgather", "recursive_doubling"):
    t = allreduce_time(name, 100e6, 256, V5E.ici_bw, V5E.ici_latency_s)
    rows.append((f"allreduce/model256/{name}/100MB", t * 1e6,
                 "closed-form v5e, 256-chip group"))
print("JSON" + json.dumps(rows))
"""


def run():
    out = run_worker(WORKER, devices=8)
    for line in out.splitlines():
        if line.startswith("JSON"):
            return [tuple(r) for r in json.loads(line[4:])]
    raise RuntimeError("no JSON output from worker")
