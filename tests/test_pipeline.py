"""Pipeline parallelism: numerical equivalence + traced signature."""
import pytest

from repro.distributed.pipeline import bubble_fraction


def test_bubble_fraction():
    assert bubble_fraction(1, 4) == pytest.approx(3 / 4)
    assert bubble_fraction(16, 4) == pytest.approx(3 / 19)
    assert bubble_fraction(64, 2) == pytest.approx(1 / 65)


def test_pipeline_matches_sequential(subproc):
    out = subproc("""
import jax
import jax.numpy as jnp
import numpy as np
from repro.distributed.pipeline import pipeline_apply
from repro.core import MeshSpec, trace_from_hlo
from repro.launch.mesh import make_mesh

P_STAGES, M, MB, D = 4, 6, 2, 32
mesh = make_mesh((4,), ("model",))
rng = np.random.default_rng(0)
w = jnp.asarray(rng.standard_normal((P_STAGES, D, D)) * 0.3, jnp.float32)
x = jnp.asarray(rng.standard_normal((M, MB, D)), jnp.float32)

def stage(wi, h):
    return jnp.tanh(h @ wi)

fn = jax.jit(lambda w, x: pipeline_apply(stage, w, x, mesh, axis="model"))
with mesh:
    compiled = fn.lower(w, x).compile()
y = fn(w, x)

# sequential reference
ref = x
for i in range(P_STAGES):
    ref = jnp.tanh(ref @ w[i])
err = float(jnp.max(jnp.abs(y - ref)))
assert err < 1e-5, err

# trace signature: collective-permute chain classified as pipeline traffic
spec = MeshSpec((4,), ("model",))
tr = trace_from_hlo(compiled.as_text(), spec, label="pipe")
perms = [e for e in tr.events if e.kind == "collective-permute"]
assert perms, "no collective-permute in pipeline trace"
assert any(e.semantic == "pipeline" for e in tr.events), \
    {e.semantic for e in tr.events}
n_hops = sum(e.multiplicity for e in perms)
assert n_hops >= M + 4 - 2   # one hop per tick (final hop is DCE'd)
print("PIPELINE_OK", err, n_hops)
""", devices=4)
    assert "PIPELINE_OK" in out
