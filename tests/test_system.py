"""End-to-end system behaviour: distributed trace -> attribution -> report,
dry-run machinery at reduced scale, loss-path equivalence."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, SHAPES, smoke_config
from repro.models import api
from repro.models.losses import cross_entropy, fused_lm_head_loss


def test_fused_loss_equals_reference():
    """Fused chunked head+xent == full-logits cross-entropy."""
    from repro.models import transformer
    cfg = smoke_config(ARCHS["chatglm3-6b"])
    params = api.init_params(cfg, 0)
    B, S = 2, 32
    batch = api.demo_batch(cfg, B, S)
    hidden, _aux = transformer.forward_hidden(cfg, params, batch,
                                              attn_impl="naive")
    targets = jnp.roll(batch["tokens"], -1, axis=1)
    mask = jnp.ones((B, S), jnp.float32).at[:, -1].set(0.0)
    fused = fused_lm_head_loss(cfg, params["embed"], hidden, targets, mask,
                               chunk=8)
    from repro.models.layers import logits_head
    logits = logits_head(cfg, params["embed"], hidden)
    ref = cross_entropy(logits, targets, mask)
    np.testing.assert_allclose(float(fused), float(ref), rtol=2e-5)


def test_fused_loss_gradients_match():
    cfg = smoke_config(ARCHS["h2o-danube-3-4b"])
    params = api.init_params(cfg, 0)
    batch = api.demo_batch(cfg, 2, 32)

    def loss_fused(p):
        return api.loss_fn(cfg, p, batch, attn_impl="naive")

    def loss_ref(p):
        from repro.models.losses import lm_loss
        logits, aux = api.forward(cfg, p, batch, attn_impl="naive")
        return lm_loss(cfg, logits, batch, aux)

    lf, gf = jax.value_and_grad(loss_fused)(params)
    lr, gr = jax.value_and_grad(loss_ref)(params)
    np.testing.assert_allclose(float(lf), float(lr), rtol=1e-4)
    for a, b in zip(jax.tree.leaves(gf), jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-2, atol=2e-3)


def test_traced_train_step_multi_device(subproc):
    """8-device mesh: trace a smoke train step; assert the multi-layer
    attribution pipeline produces grad_sync + module semantics + sane
    roofline terms (the paper's core loop, end to end)."""
    out = subproc("""
import jax
import jax.numpy as jnp
from repro.configs import ARCHS, smoke_config
from repro.core import MeshSpec, roofline, trace_from_hlo
from repro.core.report import top_contenders_table
from repro.distributed import sharding as sh
from repro.distributed.autoshard import activation_sharding
from repro.launch.mesh import make_mesh
from repro.launch.presets import StepSettings
from repro.launch.steps import make_train_step
from repro.models import api
from repro.optim import adamw

cfg = smoke_config(ARCHS["chatglm3-6b"]).replace(
    d_model=128, d_ff=256, num_layers=4, vocab_size=512, num_heads=8,
    num_kv_heads=4, head_dim=16)
mesh = make_mesh((2, 4), ("data", "model"))
spec = MeshSpec((2, 4), ("data", "model"))
opt_cfg = adamw.AdamWConfig()
st = StepSettings(accum=2, remat="full")
step = make_train_step(cfg, opt_cfg, st)
params = api.abstract_params(cfg)
f32 = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32)
opt = {"m": jax.tree.map(f32, params), "v": jax.tree.map(f32, params),
       "count": jax.ShapeDtypeStruct((), jnp.int32)}
shape = type("S", (), {"global_batch": 8, "seq_len": 64, "kind": "train"})()
batch = api.batch_specs(cfg, shape)
pspecs = sh.param_pspecs(cfg, mesh)
in_sh = (sh.named(mesh, pspecs),
         sh.named(mesh, {"m": pspecs, "v": pspecs,
                         "count": jax.sharding.PartitionSpec()}),
         None)
jfn = jax.jit(step, in_shardings=in_sh, donate_argnums=(0, 1))
with activation_sharding(mesh):
    lowered = jfn.lower(params, opt, batch)
compiled = lowered.compile()
tr = trace_from_hlo(compiled.as_text(), spec, label="smoke",
                    cost_analysis=compiled.cost_analysis(),
                    memory_analysis=compiled.memory_analysis())
assert len(tr.events) > 0, "no collectives found"
sems = {e.semantic for e in tr.events}
assert "grad_sync" in sems, sems
kinds = {e.kind for e in tr.events}
assert "all-reduce" in kinds
links = {e.link_class for e in tr.events}
assert any(l.startswith("ici.") for l in links), links
mults = [e.multiplicity for e in tr.events]
assert max(mults) >= 4, mults   # layer scan counted per-iteration
assert tr.hlo_flops > 0 and tr.hlo_bytes > 0
rf = roofline(tr, model_flops=1e9)
assert rf.bound_s > 0 and rf.dominant in ("compute", "memory", "collective")
print(top_contenders_table(tr)[:200])
print("TRACE_OK", len(tr.events), rf.dominant)
""")
    assert "TRACE_OK" in out


def test_dryrun_cell_small_mesh(subproc):
    """The dry-run driver end-to-end on an 8-device mesh (real arch)."""
    out = subproc("""
import jax
from repro.core import MeshSpec
from repro.launch.dryrun import lower_cell
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
spec = MeshSpec((2, 4), ("data", "model"))
r = lower_cell("hymba-1.5b", "decode_32k", mesh=mesh, mesh_spec=spec)
assert "skipped" not in r, r
assert r["compile_s"] > 0
assert r["n_collectives"] > 0
assert r["dominant"] in ("compute", "memory", "collective")
print("DRYRUN_OK", r["dominant"], r["mem_model_gb"])
""", devices=8)
    assert "DRYRUN_OK" in out


def test_detectors_fire_on_misconfiguration(subproc):
    """Fig 7 analogue: a sharding misconfiguration produces axis-detour
    traffic visible to the detector suite."""
    out = subproc("""
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.core import MeshSpec, trace_from_hlo
from repro.core import detect
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("data", "model"))
spec = MeshSpec((2, 4), ("data", "model"))

def step(w, x):
    h = jnp.einsum("bd,df->bf", x, w)
    return (h.astype(jnp.float32) ** 2).sum()

g = jax.grad(step)
bad = jax.jit(g, in_shardings=(NamedSharding(mesh, P("data", "model")),
                               NamedSharding(mesh, P("model", None))))
with mesh:
    compiled = bad.lower(jax.ShapeDtypeStruct((256, 512), jnp.bfloat16),
                         jax.ShapeDtypeStruct((64, 256), jnp.bfloat16)).compile()
tr = trace_from_hlo(compiled.as_text(), spec, label="bad")
assert len(tr.events) > 0
finds = detect.run_all(tr, expected_axes={"grad_sync": "data"})
print("N_EVENTS", len(tr.events), "FINDINGS", len(finds))
print("MISCONFIG_OK")
""")
    assert "MISCONFIG_OK" in out


def test_input_specs_cover_all_cells():
    """Every runnable (arch x shape) has well-formed ShapeDtypeStruct specs;
    exactly 4 documented skips out of the 40 assigned cells."""
    from repro.configs import (ARCH_ORDER, SHAPE_ORDER, get_config,
                               shape_applicable)
    n_cells = 0
    n_skipped = 0
    for arch in ARCH_ORDER:
        cfg = get_config(arch)
        for sname in SHAPE_ORDER:
            shape = SHAPES[sname]
            ok, reason = shape_applicable(cfg, shape)
            if not ok:
                n_skipped += 1
                assert reason
                continue
            specs = api.input_specs(cfg, shape)
            leaves = jax.tree.leaves(specs)
            assert all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)
            assert all(all(d > 0 for d in l.shape) for l in leaves)
            n_cells += 1
    assert n_cells + n_skipped == 40
    # long_500k skips: chatglm3/llama3/qwen2-vl/qwen3-moe (pure full
    # attention) + whisper (enc-dec audio)
    assert n_skipped == 5


def test_report_renderers():
    """ASCII/JSON/HTML renderers run on a synthetic trace."""
    from repro.core.events import CollectiveEvent, Trace
    from repro.core.topology import MeshSpec, V5E
    from repro.core import costmodel, attribution, report
    mesh = MeshSpec((2, 4), ("data", "model"))
    evs = []
    for i, kind in enumerate(["all-reduce", "all-gather", "all-to-all"]):
        ev = CollectiveEvent(
            name=f"c{i}", kind=kind, async_start=False,
            operand_bytes=1 << (18 + i), result_bytes=1 << (18 + i),
            dtype="bf16", replica_groups=[[0, 1, 2, 3], [4, 5, 6, 7]],
            group_size=4, num_groups=2,
            op_name=f"jit(f)/layer/attn/prim{i}", computation="main",
            multiplicity=i + 1)
        costmodel.annotate_event(ev, mesh, V5E)
        attribution.attribute_event(ev)
        evs.append(ev)
    tr = Trace("synthetic", mesh.shape, mesh.axes, 8, evs)
    tr.hlo_flops = 1e12
    tr.hlo_bytes = 1e10
    assert "all-reduce" in report.top_contenders_table(tr)
    assert "attention" in report.semantic_table(tr)
    assert "synthetic" in report.summary(tr)
    assert "timeline" not in report.timeline(tr)  # renders rows
    js = report.to_json(tr)
    assert '"kind": "all-reduce"' in js
    html = report.to_html(tr, mesh)
    assert "<h2>" in html and "comm matrix" in html
