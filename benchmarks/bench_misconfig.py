"""Fig 7 analogue: detecting a sharding misconfiguration.

The paper catches a NUMA misbinding that silently routed GPU traffic through
host processes (~5x slowdown).  The TPU analogue we reproduce: **inconsistent
activation annotations** — a copy-pasted `with_sharding_constraint` puts
alternate layers' residuals on different mesh axes, so every layer boundary
re-shards the activations across the full mesh.  The program is numerically
identical and compiles clean; only the traced wire pattern exposes the bug.
"""
from __future__ import annotations

import json

from _util import run_worker

WORKER = """
import json
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.core import MeshSpec, trace_from_hlo, detect
from repro.launch.mesh import make_mesh

D_AX, M_AX = 2, 4
mesh = make_mesh((D_AX, M_AX), ("data", "model"))
spec = MeshSpec((D_AX, M_AX), ("data", "model"))
L, B, S, D, F = 8, 8, 256, 512, 1024

def make_step(bug: bool):
    good = NamedSharding(mesh, P("data", None, None))
    bad = NamedSharding(mesh, P("model", None, None))
    def step(w1, w2, x):
        h = x
        for i in range(L):   # unrolled: static per-layer annotations
            with jax.named_scope("layer"):
                # stale copy-pasted annotation on alternate layers
                sh = bad if (bug and i % 2 == 1) else good
                h = jax.lax.with_sharding_constraint(h, sh)
                with jax.named_scope("mlp"):
                    z = jax.nn.silu(jnp.einsum("bsd,df->bsf", h, w1[i]))
                    h = h + jnp.einsum("bsf,fd->bsd", z, w2[i])
        with jax.named_scope("loss"):
            return (h.astype(jnp.float32) ** 2).mean()
    return step

rows = {}
out_rows = []
for label in ("good", "bad"):
    step = make_step(label == "bad")
    g = jax.jit(jax.value_and_grad(step, argnums=(0, 1)),
                in_shardings=(NamedSharding(mesh, P(None, None, "model")),
                              NamedSharding(mesh, P(None, "model", None)),
                              NamedSharding(mesh, P("data", None, None))))
    with mesh:
        compiled = g.lower(
            jax.ShapeDtypeStruct((L, D, F), jnp.bfloat16),
            jax.ShapeDtypeStruct((L, F, D), jnp.bfloat16),
            jax.ShapeDtypeStruct((B, S, D), jnp.bfloat16)).compile()
    tr = trace_from_hlo(compiled.as_text(), spec, label=label,
                        cost_analysis=compiled.cost_analysis())
    finds = detect.run_all(tr, expected_axes={"grad_sync": "data",
                                              "ffn": "model"})
    rows[label] = tr
    out_rows.append((f"misconfig/{label}", tr.total_est_time_s() * 1e6,
                     f"wireMB={tr.total_wire_bytes()/1e6:.1f}|"
                     f"collectives={sum(e.multiplicity for e in tr.events)}|"
                     f"findings={len(finds)}"))
    for f in finds[:3]:
        print(f"  [{label}] {f}")
slow = rows["bad"].total_est_time_s() / max(rows["good"].total_est_time_s(), 1e-12)
wire_ratio = rows["bad"].total_wire_bytes() / max(rows["good"].total_wire_bytes(), 1e-12)
out_rows.append(("misconfig/modeled_slowdown", slow,
                 f"wire_ratio={wire_ratio:.1f}|bad/good collective-time ratio "
                 f"(paper: ~5x for NUMA misbinding)"))
print("JSON" + json.dumps(out_rows))
"""


def run():
    out = run_worker(WORKER, devices=8)
    print("\n".join(l for l in out.splitlines() if not l.startswith("JSON")))
    for line in out.splitlines():
        if line.startswith("JSON"):
            return [tuple(r) for r in json.loads(line[4:])]
    raise RuntimeError("no JSON output from worker")
