"""Multi-run session workflow: compare one workload across configurations.

    PYTHONPATH=src python examples/session_compare.py

The paper's headline experiment shape — the same step traced under several
mesh layouts (the MPI-library / NUMA-binding analogue) — collected into a
named `TraceSession`, persisted as one artifact, reloaded, and rendered as
an n-way comparison table.  Compiles a real train step per mesh layout;
pass --synthetic to use the seeded synthetic workload instead (no jax).
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import sys

from repro.core import MeshSpec
from repro.core.session import TraceSession


def real_traces():
    import jax

    from repro.configs import ARCHS, smoke_config
    from repro.core import trace_from_hlo
    from repro.distributed import sharding as sh
    from repro.distributed.autoshard import activation_sharding
    from repro.launch.mesh import make_mesh
    from repro.launch.presets import StepSettings
    from repro.launch.steps import make_train_step
    from repro.models import api
    from repro.optim import adamw
    import jax.numpy as jnp

    traces = []
    for label, shape, axes in (
            ("dp8", (8, 1), ("data", "model")),
            ("dp4xtp2", (4, 2), ("data", "model")),
            ("dp2xtp4", (2, 4), ("data", "model"))):
        mesh = make_mesh(shape, axes)
        spec = MeshSpec(shape, axes)
        cfg = smoke_config(ARCHS["chatglm3-6b"]).replace(
            d_model=128, d_ff=256, num_layers=4, vocab_size=512,
            num_heads=8, num_kv_heads=4, head_dim=16)
        step = make_train_step(cfg, adamw.AdamWConfig(),
                               StepSettings(accum=1, remat="full"))
        params = api.abstract_params(cfg)
        f32 = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32)
        opt = {"m": jax.tree.map(f32, params), "v": jax.tree.map(f32, params),
               "count": jax.ShapeDtypeStruct((), jnp.int32)}
        bshape = type("S", (), {"global_batch": 8, "seq_len": 128,
                                "kind": "train"})()
        batch = api.batch_specs(cfg, bshape)
        pspecs = sh.param_pspecs(cfg, mesh)
        jfn = jax.jit(step, in_shardings=(
            sh.named(mesh, pspecs),
            sh.named(mesh, {"m": pspecs, "v": pspecs,
                            "count": jax.sharding.PartitionSpec()}), None),
            donate_argnums=(0, 1))
        with activation_sharding(mesh):
            compiled = jfn.lower(params, opt, batch).compile()
        traces.append(trace_from_hlo(
            compiled.as_text(), spec, label=label,
            cost_analysis=compiled.cost_analysis(),
            memory_analysis=compiled.memory_analysis()))
    return traces


def synthetic_traces():
    from repro.core.synth import synthetic_trace
    return [
        synthetic_trace("dp8", MeshSpec((8, 1), ("data", "model")),
                        n_sites=2000, seed=0),
        synthetic_trace("dp4xtp2", MeshSpec((4, 2), ("data", "model")),
                        n_sites=2000, seed=0),
        synthetic_trace("dp2xtp4", MeshSpec((2, 4), ("data", "model")),
                        n_sites=2000, seed=0),
    ]


def main():
    synthetic = "--synthetic" in sys.argv
    sess = TraceSession("mesh-layout-sweep")
    for tr in (synthetic_traces() if synthetic else real_traces()):
        sess.add(tr)
    os.makedirs("results", exist_ok=True)
    path = sess.save("results/mesh_layout_sweep.npz")
    sess = TraceSession.load(path)
    print(f"saved + reloaded '{sess.name}' "
          f"({os.path.getsize(path)//1024} KB): {sess.labels()}\n")
    print(sess.table())
    print()
    print(sess.table(by="semantic", metric="time"))
    print()
    print(sess.diff(sess.labels()[0], sess.labels()[-1]))


if __name__ == "__main__":
    main()
