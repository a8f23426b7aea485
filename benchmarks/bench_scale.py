"""Fig 8 analogue: workload communication profile vs fleet size.

The paper profiles GROMACS at 8/16 nodes and shows the PME (all-to-all)
fraction and transport switch (rc->dc) with scale.  We trace a reduced MoE
arch train step at 16/64/256 devices and report how the per-semantic
communication split and modeled step time scale.
"""
from __future__ import annotations

import json

from _util import run_worker

WORKER_TMPL = """
import json
import jax
import jax.numpy as jnp
from repro.configs import ARCHS, smoke_config
from repro.core import MeshSpec, trace_from_hlo
from repro.distributed import sharding as sh
from repro.distributed.autoshard import activation_sharding
from repro.launch.mesh import make_mesh
from repro.launch.presets import StepSettings
from repro.launch.steps import make_train_step
from repro.models import api
from repro.optim import adamw

D, M = %d, %d
mesh = make_mesh((D, M), ("data", "model"))
spec = MeshSpec((D, M), ("data", "model"))
cfg = smoke_config(ARCHS["mixtral-8x22b"]).replace(
    d_model=256, moe_d_ff=512, num_layers=4, vocab_size=1024,
    num_heads=16, num_kv_heads=8, head_dim=16, num_experts=8, top_k=2,
    window=0)
st = StepSettings(accum=1, remat="full")
opt_cfg = adamw.AdamWConfig()
step = make_train_step(cfg, opt_cfg, st)
params = api.abstract_params(cfg)
f32 = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32)
opt = {"m": jax.tree.map(f32, params), "v": jax.tree.map(f32, params),
       "count": jax.ShapeDtypeStruct((), jnp.int32)}
shape = type("S", (), {"global_batch": 4 * D, "seq_len": 256,
                       "kind": "train"})()
batch = api.batch_specs(cfg, shape)
pspecs = sh.param_pspecs(cfg, mesh)
jfn = jax.jit(step, in_shardings=(
    sh.named(mesh, pspecs),
    sh.named(mesh, {"m": pspecs, "v": pspecs,
                    "count": jax.sharding.PartitionSpec()}),
    sh.named(mesh, sh.batch_pspecs(cfg, shape, mesh))),
    donate_argnums=(0, 1))
with activation_sharding(mesh):
    compiled = jfn.lower(params, opt, batch).compile()
tr = trace_from_hlo(compiled.as_text(), spec, label=f"{D}x{M}",
                    cost_analysis=compiled.cost_analysis())
sem = tr.by_semantic()
tot = sum(a["bytes"] for a in sem.values()) or 1.0
split = "|".join(f"{k}={100*a['bytes']/tot:.0f}%%"
                 for k, a in sorted(sem.items(), key=lambda kv: -kv[1]["bytes"])[:4])
print("JSON" + json.dumps([
    (f"scale/{D*M}dev/moe_train", tr.total_est_time_s() * 1e6,
     f"{split}|wireMB={tr.total_wire_bytes()/1e6:.1f}")]))
"""


def run():
    rows = []
    for d, m in ((4, 4), (8, 8), (16, 16)):
        out = run_worker(WORKER_TMPL % (d, m), devices=d * m, timeout=560)
        for line in out.splitlines():
            if line.startswith("JSON"):
                rows += [tuple(r) for r in json.loads(line[4:])]
    return rows
