"""Checkpoint store: roundtrip, atomicity, pruning, elastic restore."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro import checkpoint


def tree(seed=0):
    r = np.random.default_rng(seed)
    return {"a": jnp.asarray(r.standard_normal((4, 8)), jnp.float32),
            "b": {"w": jnp.asarray(r.standard_normal((3,)), jnp.bfloat16),
                  "n": jnp.asarray(7, jnp.int32)}}


def test_roundtrip(tmp_path):
    t = tree()
    checkpoint.save(str(tmp_path), 5, t, extra={"next_step": 5})
    restored, extra = checkpoint.restore(str(tmp_path), t)
    assert extra["next_step"] == 5
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_latest_pointer_and_prune(tmp_path):
    t = tree()
    for step in (1, 2, 3, 4):
        checkpoint.save(str(tmp_path), step, t)
    assert checkpoint.latest_step(str(tmp_path)) == 4
    checkpoint.prune_old(str(tmp_path), keep=2)
    names = sorted(n for n in os.listdir(tmp_path) if n.startswith("step_"))
    assert names == ["step_00000003", "step_00000004"]
    assert checkpoint.latest_step(str(tmp_path)) == 4


def test_crash_mid_write_never_corrupts(tmp_path):
    """A leftover .tmp dir (simulated crash) is invisible to restore."""
    t = tree()
    checkpoint.save(str(tmp_path), 1, t, extra={"next_step": 1})
    # simulate a crashed write of step 2
    os.makedirs(tmp_path / "step_00000002.tmp")
    with open(tmp_path / "step_00000002.tmp" / "arr_00000.npy", "w") as f:
        f.write("garbage")
    assert checkpoint.latest_step(str(tmp_path)) == 1
    restored, extra = checkpoint.restore(str(tmp_path), t)
    assert extra["next_step"] == 1


def test_shape_mismatch_rejected(tmp_path):
    t = tree()
    checkpoint.save(str(tmp_path), 1, t)
    bad = {"a": jnp.zeros((4, 9)), "b": t["b"]}
    with pytest.raises(ValueError):
        checkpoint.restore(str(tmp_path), bad)


@given(seed=st.integers(0, 1000))
@settings(max_examples=8, deadline=None)
def test_roundtrip_property(tmp_path_factory, seed):
    d = tmp_path_factory.mktemp(f"ck{seed}")
    t = tree(seed)
    checkpoint.save(str(d), 0, t)
    restored, _ = checkpoint.restore(str(d), t)
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_async_checkpointer(tmp_path):
    """Async save overlaps serialization; wait() surfaces results + errors."""
    from repro.checkpoint import AsyncCheckpointer
    t = tree()
    ck = AsyncCheckpointer(str(tmp_path), keep=2)
    for step in (1, 2, 3):
        ck.save(step, t, extra={"next_step": step})
    ck.wait()
    assert checkpoint.latest_step(str(tmp_path)) == 3
    restored, extra = checkpoint.restore(str(tmp_path), t)
    assert extra["next_step"] == 3
    names = sorted(n for n in os.listdir(tmp_path) if n.startswith("step_"))
    assert len(names) == 2       # pruned to keep=2


def test_elastic_restore_across_meshes(subproc):
    """Save sharded on a (2,4) mesh, restore onto (4,2) and (8,1) meshes."""
    out = subproc("""
import jax
import jax.numpy as jnp
import numpy as np
import tempfile
import os
from jax.sharding import PartitionSpec as P, NamedSharding
from repro import checkpoint
from repro.launch.mesh import make_mesh

d = tempfile.mkdtemp()
mesh_a = make_mesh((2, 4), ("data", "model"))
x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
xa = jax.device_put(x, NamedSharding(mesh_a, P("data", "model")))
checkpoint.save(d, 1, {"x": xa})

for shape in [(4, 2), (8, 1), (1, 8)]:
    mesh_b = make_mesh(shape, ("data", "model"))
    sh = {"x": NamedSharding(mesh_b, P("data", "model"))}
    restored, _ = checkpoint.restore(d, {"x": x}, shardings=sh)
    np.testing.assert_array_equal(np.asarray(restored["x"]), np.asarray(x))
    assert restored["x"].sharding.mesh.shape["data"] == shape[0]
print("ELASTIC_OK")
""")
    assert "ELASTIC_OK" in out
