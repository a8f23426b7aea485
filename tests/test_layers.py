"""Layer-level unit tests: RoPE variants, norms, embeddings, losses."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.configs import ARCHS, smoke_config
from repro.models import layers as L
from repro.models.meta import materialize


def _cfg(**kw):
    return smoke_config(ARCHS["chatglm3-6b"]).replace(**kw)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def test_rope_is_rotation():
    """RoPE preserves norms (pure rotation) and position-0 is identity."""
    cfg = _cfg(rope="standard", rope_fraction=1.0)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 8, 4, 16)),
                    jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32), (2, 8))
    y = L.apply_rope(cfg, x, pos)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(y), axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5)
    y0 = L.apply_rope(cfg, x, jnp.zeros((2, 8), jnp.int32))
    np.testing.assert_allclose(np.asarray(y0), np.asarray(x), atol=1e-6)


def test_rope_relative_property():
    """<rope(q,m), rope(k,n)> depends only on m-n (the defining property)."""
    cfg = _cfg(rope="standard", rope_fraction=1.0)
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, 1, 1, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 1, 1, 32)), jnp.float32)

    def dot_at(m, n):
        qm = L.apply_rope(cfg, q, jnp.full((1, 1), m, jnp.int32))
        kn = L.apply_rope(cfg, k, jnp.full((1, 1), n, jnp.int32))
        return float(jnp.sum(qm * kn))

    assert dot_at(5, 3) == pytest.approx(dot_at(12, 10), rel=1e-4)
    assert dot_at(7, 7) == pytest.approx(dot_at(0, 0), rel=1e-4)


def test_partial_rope_leaves_pass_dims():
    """chatglm 2d rope rotates only the first half of head_dim."""
    cfg = _cfg(rope="partial", rope_fraction=0.5)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((1, 4, 2, 16)),
                    jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(4, dtype=jnp.int32), (1, 4))
    y = L.apply_rope(cfg, x, pos)
    np.testing.assert_array_equal(np.asarray(y[..., 8:]),
                                  np.asarray(x[..., 8:]))
    assert not np.allclose(np.asarray(y[..., :8])[0, 1:],
                           np.asarray(x[..., :8])[0, 1:])


def test_mrope_sections_equal_std_rope_when_positions_identical():
    """With t==h==w position ids, M-RoPE == standard RoPE."""
    cfg = _cfg(rope="mrope", mrope_sections=(4, 2, 2))   # head_dim 16
    x = jnp.asarray(np.random.default_rng(3).standard_normal((1, 6, 2, 16)),
                    jnp.float32)
    pos1 = jnp.broadcast_to(jnp.arange(6, dtype=jnp.int32), (1, 6))
    pos3 = jnp.broadcast_to(pos1, (3, 1, 6))
    y_m = L.apply_rope(cfg, x, pos3)
    y_s = L.apply_rope(cfg.replace(rope="standard", rope_fraction=1.0),
                       x, pos1)
    np.testing.assert_allclose(np.asarray(y_m), np.asarray(y_s), atol=2e-5)


def test_mrope_distinct_axes_differ():
    cfg = _cfg(rope="mrope", mrope_sections=(4, 2, 2))
    x = jnp.ones((1, 4, 1, 16), jnp.float32)
    same = jnp.broadcast_to(jnp.arange(4, dtype=jnp.int32), (3, 1, 4))
    mixed = same.at[1].set(same[1] * 2)
    assert not np.allclose(np.asarray(L.apply_rope(cfg, x, same)),
                           np.asarray(L.apply_rope(cfg, x, mixed)))


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

@given(seed=st.integers(0, 100), d=st.sampled_from([16, 64, 128]))
@settings(max_examples=15, deadline=None)
def test_rmsnorm_properties(seed, d):
    cfg = _cfg(norm="rmsnorm")
    p = {"scale": jnp.ones((d,))}
    x = jnp.asarray(np.random.default_rng(seed).standard_normal((2, 3, d)) * 5,
                    jnp.float32)
    y = L.apply_norm(cfg, p, x)
    ms = np.mean(np.square(np.asarray(y)), axis=-1)
    np.testing.assert_allclose(ms, 1.0, rtol=5e-2)      # unit RMS
    # scale equivariance
    y2 = L.apply_norm(cfg, p, x * 7.0)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y), rtol=5e-2,
                               atol=5e-2)


def test_layernorm_matches_numpy():
    cfg = _cfg(norm="layernorm")
    d = 32
    p = {"scale": jnp.full((d,), 1.5), "bias": jnp.full((d,), 0.25)}
    x = jnp.asarray(np.random.default_rng(5).standard_normal((2, 4, d)),
                    jnp.float32)
    y = np.asarray(L.apply_norm(cfg, p, x))
    xn = np.asarray(x)
    ref = (xn - xn.mean(-1, keepdims=True)) / np.sqrt(
        xn.var(-1, keepdims=True) + 1e-6) * 1.5 + 0.25
    np.testing.assert_allclose(y, ref, atol=2e-3)


# --------------------------------------------------------------------------
# embeddings
# --------------------------------------------------------------------------

# families that train through `api.loss_fn` with an untied input table
EMBED_GRAD_ARCHS = ["chatglm3-6b", "mixtral-8x22b"]
# 2 x 600 tokens cross the old 256-token chunk edges at 256 and 512; ids
# below 40 repeat within and across chunks, ids 40.. never occur
EMBED_B, EMBED_S, EMBED_USED = 2, 600, 40


def _embed_grad_case(arch):
    from repro.models import api
    cfg = smoke_config(ARCHS[arch])
    assert not cfg.tie_embeddings
    params = api.init_params(cfg, 0)
    tokens = np.random.default_rng(6).integers(
        0, EMBED_USED, (EMBED_B, EMBED_S)).astype(np.int32)
    tokens[:, 250:262] = 7                  # one id on both sides of 256
    tokens[0, 512], tokens[1, 511] = 3, 3   # and across 512, between rows
    return cfg, params, {"tokens": jnp.asarray(tokens)}


@pytest.mark.parametrize("arch", EMBED_GRAD_ARCHS)
def test_input_table_grad_is_row_scatter_add(arch, monkeypatch):
    """d loss / d in_table is the f32 sum of each token's row cotangent,
    and exactly zero on the rows of ids that never occur.

    The row cotangents come from the same program: a zero probe added to
    the lookup's output has the lookup's output cotangent as its gradient.
    """
    from repro.models import api
    cfg, params, batch = _embed_grad_case(arch)
    cdt = jnp.dtype(cfg.compute_dtype)
    probe = jnp.zeros((EMBED_B, EMBED_S, cfg.d_model), cdt)
    embed = L.embed_tokens

    def probed_loss(params, probe):
        monkeypatch.setattr(L, "embed_tokens",
                            lambda *a, **k: embed(*a, **k) + probe)
        return api.loss_fn(cfg, params, batch)

    g_params, g_rows = jax.jit(jax.grad(probed_loss, argnums=(0, 1)))(
        params, probe)
    got = np.asarray(g_params["embed"]["in_table"])
    assert got.dtype == np.float32
    ref = np.zeros(got.shape, np.float64)
    np.add.at(ref, np.asarray(batch["tokens"]),
              np.asarray(g_rows, np.float64))
    assert np.abs(ref[:EMBED_USED]).min(axis=1).max() > 0
    # f32 sums of up to ~60 rows against float64: summation order only
    np.testing.assert_allclose(got, ref, rtol=0,
                               atol=1e-5 * np.abs(ref).max())
    assert not got[EMBED_USED:].any()


def _tainted_dots_and_loops(jaxpr, tainted):
    """Equations of `jaxpr` (recursing into sub-jaxprs) that take a tainted
    var, or its dtype cast, as an operand: (dot_general, loops) counts."""
    from jax.extend import core as jcore
    dots = loops = 0
    tainted = set(tainted)
    for eqn in jaxpr.eqns:
        hit = any(v in tainted for v in eqn.invars
                  if not isinstance(v, jcore.Literal))
        name = eqn.primitive.name
        if hit and name == "convert_element_type":
            tainted.update(eqn.outvars)
        if hit and name == "dot_general":
            dots += 1
        if hit and name in ("scan", "while"):
            loops += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            # operands map onto a sub-jaxpr's trailing invars (scan: consts,
            # carry, xs; while: cond consts, body consts, carry)
            n = len(sub.invars)
            inner = {iv for ov, iv in zip(eqn.invars[-n:], sub.invars)
                     if not isinstance(ov, jcore.Literal) and ov in tainted}
            if inner:
                d, lo = _tainted_dots_and_loops(sub, inner)
                dots, loops = dots + d, loops + lo
    return dots, loops


@pytest.mark.parametrize("arch", EMBED_GRAD_ARCHS)
def test_train_step_embeds_by_lookup(arch):
    """The train step's program multiplies nothing by the input table and
    loops over nothing that holds it: no one-hot product, no chunked scan
    carrying the table's gradient."""
    from repro.launch.presets import StepSettings
    from repro.launch.steps import make_train_step
    from repro.optim import AdamWConfig, adamw
    cfg, params, batch = _embed_grad_case(arch)
    opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=10)
    opt = adamw.init(opt_cfg, params)
    step = make_train_step(cfg, opt_cfg, StepSettings(remat="full"))
    closed = jax.make_jaxpr(step)(params, opt, batch)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path((params, opt, batch))[0]]
    table = closed.jaxpr.invars[paths.index("[0]['embed']['in_table']")]
    dots, loops = _tainted_dots_and_loops(closed.jaxpr, {table})
    assert (dots, loops) == (0, 0)
    # the walk does find a table that is multiplied inside a loop: the head
    head = closed.jaxpr.invars[paths.index("[0]['embed']['out_head']")]
    assert all(_tainted_dots_and_loops(closed.jaxpr, {head}))


# --------------------------------------------------------------------------
# trace diff (paper before/after workflow)
# --------------------------------------------------------------------------

def test_trace_diff():
    from repro.core import costmodel, attribution
    from repro.core.diff import diff_traces, render_diff
    from repro.core.events import CollectiveEvent, Trace
    from repro.core.topology import MeshSpec, V5E

    mesh = MeshSpec((2, 4), ("data", "model"))

    def mk(nbytes, kind="all-reduce", mult=1):
        ev = CollectiveEvent(
            name="x", kind=kind, async_start=False, operand_bytes=nbytes,
            result_bytes=nbytes, dtype="bf16",
            replica_groups=[[0, 1, 2, 3], [4, 5, 6, 7]], group_size=4,
            num_groups=2, op_name="jit(f)/layer/mlp/psum",
            computation="main", multiplicity=mult)
        costmodel.annotate_event(ev, mesh, V5E)
        attribution.attribute_event(ev)
        return ev

    a = Trace("before", mesh.shape, mesh.axes, 8, [mk(1 << 24), mk(1 << 20)])
    b = Trace("after", mesh.shape, mesh.axes, 8,
              [mk(1 << 23), mk(1 << 20, kind="all-gather")])
    rows = diff_traces(a, b)
    by_key = {r.key: r for r in rows}
    ar = by_key["all-reduce|ici.model"]
    assert ar.verdict().startswith("SHRANK")
    assert by_key["all-gather|ici.model"].verdict() == "NEW"
    txt = render_diff(a, b)
    assert "SHRANK" in txt and "NEW" in txt and "TOTAL" in txt


def test_overlapped_bound_leq_serialized():
    from repro.core import costmodel
    from repro.core.events import CollectiveEvent, Trace
    from repro.core.topology import MeshSpec, V5E
    mesh = MeshSpec((2, 4), ("data", "model"))
    evs = []
    for i, groups in enumerate(([[0, 1, 2, 3], [4, 5, 6, 7]],
                                [[0, 4], [1, 5], [2, 6], [3, 7]])):
        ev = CollectiveEvent(
            name=f"e{i}", kind="all-reduce", async_start=False,
            operand_bytes=1 << 22, result_bytes=1 << 22, dtype="f32",
            replica_groups=groups, group_size=len(groups[0]),
            num_groups=len(groups), op_name="", computation="main")
        costmodel.annotate_event(ev, mesh, V5E)
        evs.append(ev)
    tr = Trace("t", mesh.shape, mesh.axes, 8, evs)
    assert tr.overlapped_est_time_s() <= tr.total_est_time_s()
    assert tr.overlapped_est_time_s() > 0
