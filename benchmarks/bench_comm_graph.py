"""Fig 6 + Table II analogue: communication graphs + top-contenders of real
model steps (dense vs MoE — the paper's Hook_1498 vs nd24k contrast maps to
few-big-transfers vs many-small-transfers).

Emits the bytes% (count%) per (HLO collective x link class) table — the
direct Table II reproduction — for a dense and a MoE arch train step.
"""
from __future__ import annotations

import json

from _util import run_worker

WORKER = """
import json
import jax
import jax.numpy as jnp
from repro.configs import ARCHS, smoke_config
from repro.core import MeshSpec, trace_from_hlo
from repro.core.report import top_contenders_table, semantic_table
from repro.distributed import sharding as sh
from repro.distributed.autoshard import activation_sharding
from repro.launch.mesh import make_mesh
from repro.launch.presets import StepSettings
from repro.launch.steps import make_train_step
from repro.models import api
from repro.optim import adamw

mesh = make_mesh((2, 4), ("data", "model"))
spec = MeshSpec((2, 4), ("data", "model"))
rows = []
for arch in ("chatglm3-6b", "mixtral-8x22b"):
    cfg = smoke_config(ARCHS[arch]).replace(
        d_model=128, d_ff=256, moe_d_ff=256 if ARCHS[arch].num_experts else 0,
        num_layers=4, vocab_size=512, num_heads=8, num_kv_heads=4, head_dim=16)
    st = StepSettings(accum=1, remat="full")
    opt_cfg = adamw.AdamWConfig()
    step = make_train_step(cfg, opt_cfg, st)
    params = api.abstract_params(cfg)
    f32 = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32)
    opt = {"m": jax.tree.map(f32, params), "v": jax.tree.map(f32, params),
           "count": jax.ShapeDtypeStruct((), jnp.int32)}
    shape = type("S", (), {"global_batch": 8, "seq_len": 128, "kind": "train"})()
    batch = api.batch_specs(cfg, shape)
    pspecs = sh.param_pspecs(cfg, mesh)
    jfn = jax.jit(step, in_shardings=(
        sh.named(mesh, pspecs),
        sh.named(mesh, {"m": pspecs, "v": pspecs,
                        "count": jax.sharding.PartitionSpec()}), None),
        donate_argnums=(0, 1))
    with activation_sharding(mesh):
        compiled = jfn.lower(params, opt, batch).compile()
    tr = trace_from_hlo(compiled.as_text(), spec, label=arch,
                        cost_analysis=compiled.cost_analysis(),
                        memory_analysis=compiled.memory_analysis())
    print(f"=== {arch} top contenders (Table II analogue) ===")
    print(top_contenders_table(tr))
    print(f"=== {arch} semantic (MPI-layer) rollup ===")
    print(semantic_table(tr))
    agg = tr.by_kind_and_link()
    total_b = sum(a["bytes"] for a in agg.values()) or 1.0
    top = max(agg.items(), key=lambda kv: kv[1]["bytes"])
    n_ev = sum(e.multiplicity for e in tr.events)
    a2a = sum(a["bytes"] for k, a in agg.items() if "all-to-all" in k)
    rows.append((f"commgraph/{arch}", float(n_ev),
                 f"top={top[0]}@{100*top[1]['bytes']/total_b:.0f}%|"
                 f"a2a_bytes%={100*a2a/total_b:.1f}|"
                 f"collGB={total_b/1e9:.3f}"))
print("JSON" + json.dumps(rows))
"""


def run():
    out = run_worker(WORKER, devices=8)
    print("\n".join(l for l in out.splitlines() if not l.startswith("JSON")))
    for line in out.splitlines():
        if line.startswith("JSON"):
            return [tuple(r) for r in json.loads(line[4:])]
    raise RuntimeError("no JSON output from worker")
