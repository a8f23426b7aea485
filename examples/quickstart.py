"""Quickstart: trace the communication of a sharded training step.

    PYTHONPATH=src python examples/quickstart.py

Builds an 8-device host mesh, compiles one train step of a reduced dense LM,
and prints the multi-layer trace: top-contenders (Table II analogue),
semantic rollup (MPI-layer analogue), modeled timeline and roofline terms.
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, smoke_config
from repro.core import MeshSpec, roofline, trace_from_hlo
from repro.core.report import (semantic_table, summary, timeline,
                               top_contenders_table)
from repro.distributed import sharding as sh
from repro.distributed.autoshard import activation_sharding
from repro.launch.mesh import make_mesh
from repro.launch.presets import StepSettings
from repro.launch.steps import make_train_step
from repro.models import api
from repro.optim import adamw


def main():
    cfg = smoke_config(ARCHS["chatglm3-6b"]).replace(
        d_model=256, d_ff=512, num_layers=6, vocab_size=1024,
        num_heads=8, num_kv_heads=4, head_dim=32)
    mesh = make_mesh((2, 4), ("data", "model"))
    spec = MeshSpec((2, 4), ("data", "model"))

    step = make_train_step(cfg, adamw.AdamWConfig(),
                           StepSettings(accum=2, remat="full"))
    params = api.abstract_params(cfg)
    f32 = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32)
    opt = {"m": jax.tree.map(f32, params), "v": jax.tree.map(f32, params),
           "count": jax.ShapeDtypeStruct((), jnp.int32)}
    shape = type("S", (), {"global_batch": 8, "seq_len": 256,
                           "kind": "train"})()
    batch = api.batch_specs(cfg, shape)
    pspecs = sh.param_pspecs(cfg, mesh)
    jfn = jax.jit(step, donate_argnums=(0, 1), in_shardings=(
        sh.named(mesh, pspecs),
        sh.named(mesh, {"m": pspecs, "v": pspecs,
                        "count": jax.sharding.PartitionSpec()}),
        sh.named(mesh, sh.batch_pspecs(cfg, shape, mesh))))

    print("lowering + compiling one train step on a 2x4 mesh ...")
    with activation_sharding(mesh):
        compiled = jfn.lower(params, opt, batch).compile()

    trace = trace_from_hlo(compiled.as_text(), spec, label="quickstart",
                           cost_analysis=compiled.cost_analysis(),
                           memory_analysis=compiled.memory_analysis())
    print()
    print(summary(trace))
    print("\n--- top contenders (collective kind x link class) ---")
    print(top_contenders_table(trace))
    print("\n--- semantic rollup (grad_sync / attention / ffn / ...) ---")
    print(semantic_table(trace))
    print("\n--- modeled timeline (heaviest collectives) ---")
    print(timeline(trace, top=10))
    rf = roofline(trace, model_flops=6.0 * api.flops_param_count(cfg)
                  * shape.global_batch * shape.seq_len)
    print(f"\nroofline: compute {rf.compute_s*1e3:.2f} ms | memory "
          f"{rf.memory_s*1e3:.2f} ms | collective {rf.collective_s*1e3:.2f} ms"
          f" -> dominant: {rf.dominant} (mfu bound {rf.model_roofline_fraction:.3f})")


if __name__ == "__main__":
    main()
