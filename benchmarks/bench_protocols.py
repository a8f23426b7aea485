"""Fig 4 analogue: protocol regimes (eager vs rendezvous) across message
sizes and collective kinds.

The paper sweeps UCX configs to expose eager/rndv crossover and get/put
schemes; we sweep payload sizes per collective kind, measure host wall time
on an 8-device mesh, and derive the v5e cost-model completion time + regime
classification (latency- vs bandwidth-bound) from the tracer.
"""
from __future__ import annotations

import json

from _util import run_worker

WORKER = """
import functools
import json
import time
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.core import MeshSpec, trace_from_hlo
from repro.launch.mesh import make_mesh

mesh = make_mesh((8,), ("model",))
spec = MeshSpec((8,), ("model",))

KINDS = {
    "all-reduce": (lambda s: jax.lax.psum(s, "model"), P(None)),
    "all-gather": (lambda s: jax.lax.all_gather(s, "model"), P(None)),
    "reduce-scatter": (lambda s: jax.lax.psum_scatter(
        s.reshape(8, -1), "model", scatter_dimension=0), P("model")),
    "all-to-all": (lambda s: jax.lax.all_to_all(
        s.reshape(8, -1), "model", 0, 0), P("model")),
}

rows = []
for log2 in (10, 14, 18, 22, 26):
    nbytes = 1 << log2
    n_elems = max(nbytes // 4, 64)
    x = jnp.zeros((8, n_elems // 8), jnp.float32)
    xd = jax.device_put(x, NamedSharding(mesh, P("model")))
    for kind, (f, out_spec) in KINDS.items():
        fn = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("model"),
                               out_specs=out_spec, check_vma=False))
        compiled = fn.lower(xd).compile()
        for _ in range(2):
            out = fn(xd)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(5):
            out = fn(xd)
        jax.block_until_ready(out)
        us = (time.perf_counter() - t0) / 5 * 1e6
        tr = trace_from_hlo(compiled.as_text(), spec, label=kind)
        if tr.events:
            ev = max(tr.events, key=lambda e: e.operand_bytes)
            derived = f"v5e={ev.est_time_s*1e6:.2f}us|{ev.protocol}|{ev.link_class}"
        else:
            derived = "no-collective"
        rows.append((f"proto/{kind}/{nbytes}B", us, derived))
print("JSON" + json.dumps(rows))
"""


def run():
    out = run_worker(WORKER, devices=8)
    for line in out.splitlines():
        if line.startswith("JSON"):
            return [tuple(r) for r in json.loads(line[4:])]
    raise RuntimeError("no JSON output from worker")
