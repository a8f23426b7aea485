"""Sort-based EP dispatch vs the einsum baseline (numerical equivalence
with a no-drop capacity factor, on a real mesh)."""


def test_sort_dispatch_matches_einsum(subproc):
    out = subproc("""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.configs import ARCHS, smoke_config
from repro.distributed.autoshard import activation_sharding
from repro.launch.mesh import make_mesh
from repro.models import api, moe as moe_mod
from repro.models.meta import materialize

cfg = smoke_config(ARCHS["qwen3-moe-235b-a22b"]).replace(
    num_experts=8, top_k=2,
    capacity_factor=4.0)     # = E/k: no drops in either path
mesh = make_mesh((2, 4), ("data", "model"))

meta = moe_mod.moe_meta(cfg)
params = materialize(meta, jax.random.PRNGKey(0))
x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, cfg.d_model),
                      jnp.float32).astype(jnp.bfloat16)

def run(dispatch):
    c = cfg.replace(moe_dispatch=dispatch)
    def f(p, xx):
        y, aux = moe_mod.apply_moe(c, p, xx)
        return y, aux
    with activation_sharding(mesh):
        xd = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
        pd = jax.device_put(params, NamedSharding(mesh, P()))
        y, aux = jax.jit(f)(pd, xd)
    return np.asarray(y, np.float32), float(aux)

y_e, aux_e = run("einsum")
y_s, aux_s = run("sort")
err = np.max(np.abs(y_e - y_s)) / (np.abs(y_e).max() + 1e-6)
print("REL_ERR", err, "AUX", aux_e, aux_s)
assert err < 0.03, err
assert abs(aux_e - aux_s) < 0.2, (aux_e, aux_s)

# gradients flow through the sort path
def loss(p):
    c = cfg.replace(moe_dispatch="sort")
    with activation_sharding(mesh):
        y, aux = moe_mod.apply_moe(c, p, x)
    return (y.astype(jnp.float32) ** 2).mean() + 0.01 * aux
g = jax.grad(loss)(params)
gn = sum(float(jnp.abs(l).sum()) for l in jax.tree.leaves(g))
assert np.isfinite(gn) and gn > 0
print("GRAD_OK", gn)
print("MOE_EP_OK")
""")
    assert "MOE_EP_OK" in out
