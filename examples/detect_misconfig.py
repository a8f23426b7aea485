"""Fig 7 walkthrough: catch a silent sharding misconfiguration.

    PYTHONPATH=src python examples/detect_misconfig.py

Two numerically-identical programs; one has a stale sharding annotation on
alternate layers.  Both compile and train fine — only the traced wire
pattern shows that activations ping-pong across the mesh every layer.
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import MeshSpec, detect, trace_from_hlo
from repro.core.report import top_contenders_table
from repro.launch.mesh import make_mesh

L, B, S, D, F = 8, 8, 256, 512, 1024


def make_step(mesh, bug: bool):
    good = NamedSharding(mesh, P("data", None, None))
    bad = NamedSharding(mesh, P("model", None, None))

    def step(w1, w2, x):
        h = x
        for i in range(L):
            with jax.named_scope("layer"):
                h = jax.lax.with_sharding_constraint(
                    h, bad if (bug and i % 2 == 1) else good)
                with jax.named_scope("mlp"):
                    z = jax.nn.silu(jnp.einsum("bsd,df->bsf", h, w1[i]))
                    h = h + jnp.einsum("bsf,fd->bsd", z, w2[i])
        return (h.astype(jnp.float32) ** 2).mean()
    return step


def main():
    mesh = make_mesh((2, 4), ("data", "model"))
    spec = MeshSpec((2, 4), ("data", "model"))
    for label in ("good", "bad"):
        g = jax.jit(jax.value_and_grad(make_step(mesh, label == "bad"),
                                       argnums=(0, 1)),
                    in_shardings=(NamedSharding(mesh, P(None, None, "model")),
                                  NamedSharding(mesh, P(None, "model", None)),
                                  NamedSharding(mesh, P("data", None, None))))
        with mesh:
            compiled = g.lower(
                jax.ShapeDtypeStruct((L, D, F), jnp.bfloat16),
                jax.ShapeDtypeStruct((L, F, D), jnp.bfloat16),
                jax.ShapeDtypeStruct((B, S, D), jnp.bfloat16)).compile()
        tr = trace_from_hlo(compiled.as_text(), spec, label=label)
        print(f"\n=== {label} config ===")
        print(top_contenders_table(tr))
        print(f"modeled collective time: {tr.total_est_time_s()*1e6:.0f} us, "
              f"wire {tr.total_wire_bytes()/1e6:.1f} MB")
        for f in detect.run_all(tr, expected_axes={"grad_sync": "data",
                                                   "ffn": "model"})[:5]:
            print(" ", f)


if __name__ == "__main__":
    main()
