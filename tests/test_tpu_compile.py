"""Compiles for a described, unattached TPU v5e (v5e:2x2).

Nothing here runs: each test hands the chip's own compiler the kernels at
published widths, or the chatglm3-6b train steps that `chip_smoke.py`
runs, and checks what it reports — that it accepts the program, how many
bytes a device holds, which collectives it put in.  The topology is
described inside a module fixture, never at import, so every xdist worker
collects the same tests and only the worker given this file loads libtpu.
Keep every compile for the described chip in this one file.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import MeshSpec
from repro.core.topology import V5E
from repro.core.tracer import trace_compiled
from repro.distributed.autoshard import activation_sharding
from repro.kernels import ops
from repro.kernels.mamba_scan import mamba_scan
from repro.launch.mesh import make_mesh
from repro.launch.presets import StepSettings
from repro.launch.train import Trainer
from repro.models import api
from repro.optim import adamw

# the bring-up size `chip_smoke.py` runs: every published chatglm3-6b
# width, depth cut to 2 layers so params + AdamW state fit 16 GB of HBM
BRINGUP_LAYERS, BRINGUP_SEQ = 2, 2048
SETTINGS = StepSettings(remat="full", opt_state_dtype="bfloat16")
# what the compiler lets one v5e program hold (15.75 GiB)
HBM_LIMIT = 15.75 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a described-chip compile is written to the cache but cannot be read
    # back without the chip: keep the cache off around these compiles
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    log_was = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:   # no libtpu here: nothing can be described
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        if log_was is None:
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(tree, shardings):
    """ShapeDtypeStructs of `tree` placed on `shardings` (one or a tree)."""
    if not isinstance(shardings, dict):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=shardings), tree)
    return jax.tree.map(lambda s, h: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=h), tree, shardings)


def _trainer_step_specs(trainer, batch, seq):
    params = api.abstract_params(trainer.cfg)
    opt = jax.eval_shape(lambda p: adamw.init(trainer.opt_cfg, p), params)
    return params, opt, {"tokens": jax.ShapeDtypeStruct((batch, seq),
                                                        jnp.int32)}


def test_flash_attention_chatglm3_causal(one_chip):
    cfg = get_config("chatglm3-6b")
    S, H, K, D = 4096, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jax.ShapeDtypeStruct((1, S, H, D), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, S, K, D), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(lambda q, k, v: ops.flash_attention(
        cfg, q, k, v, causal=True)).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_windowed_danube(one_chip):
    """head_dim 120 is padded to 128 by the wrapper; 4096 window at 8k."""
    cfg = get_config("h2o-danube-3-4b")
    S, H, K, D = 8192, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    assert D % 128
    q = jax.ShapeDtypeStruct((1, S, H, D), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, S, K, D), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(lambda q, k, v: ops.flash_attention(
        cfg, q, k, v, causal=True, window=cfg.window)).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_mamba_scan_falcon_mamba_width(one_chip):
    """Default tiles at d_inner 8192, N 16 fit VMEM and the (8,128) tiling."""
    cfg = get_config("falcon-mamba-7b")
    B, S, Di, N = 1, 2048, cfg.d_inner, cfg.ssm_state
    ab = jax.ShapeDtypeStruct((B, S, Di, N), jnp.float32, sharding=one_chip)
    c = jax.ShapeDtypeStruct((B, S, N), jnp.float32, sharding=one_chip)
    compiled = jax.jit(mamba_scan).lower(ab, ab, c).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_train_step_one_chip_bringup(one_chip):
    """The trainer's one-chip step at the bring-up size fits one v5e."""
    cfg = get_config("chatglm3-6b").replace(num_layers=BRINGUP_LAYERS)
    tr = Trainer(cfg, steps=5, batch=1, seq=BRINGUP_SEQ, settings=SETTINGS)
    specs = _on(_trainer_step_specs(tr, 1, BRINGUP_SEQ), one_chip)
    compiled = tr.jit_step.lower(*specs).compile()
    ma = compiled.memory_analysis()
    held = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    assert 0 < held <= HBM_LIMIT, held
    trace = trace_compiled(compiled, MeshSpec((1,), ("data",)), shards=1)
    assert len(trace.events) == 0          # one chip: no collectives
    assert trace.hlo_flops > 0


@pytest.mark.parametrize("accum", [1, 2])
def test_train_step_2x2_trace(topo, accum):
    """FSDP+TP step on the 2x2 mesh: the trace shows the grad_sync
    all-reduce over `data` and the all-gathers over `model`, and both
    ingest engines build the same store.  With `accum` 2 the input table's
    lookup runs inside the accumulation scan on scan-sliced tokens."""
    cfg = get_config("chatglm3-6b").replace(num_layers=BRINGUP_LAYERS)
    mesh = make_mesh((2, 2), ("data", "model"), devices=topo.devices)
    batch_size = 2 * accum
    tr = Trainer(cfg, steps=3, batch=batch_size, seq=BRINGUP_SEQ, mesh=mesh,
                 settings=dataclasses.replace(SETTINGS, accum=accum))
    params, opt, batch = _trainer_step_specs(tr, batch_size, BRINGUP_SEQ)
    with activation_sharding(mesh):
        compiled = tr.jit_step.lower(_on(params, tr.param_sh),
                                     _on(opt, tr.opt_sh), batch).compile()
    spec = MeshSpec((2, 2), ("data", "model"))
    col = trace_compiled(compiled, spec, shards=1)
    rows = trace_compiled(compiled, spec, engine="rows")
    assert col.store.identical(rows.store)
    sites = {(e.kind, e.link_class, e.semantic) for e in col.events}
    assert ("all-reduce", "ici.data", "grad_sync") in sites, sites
    assert any(k == "all-gather" and link == "ici.model"
               for k, link, _ in sites), sites
    assert col.per_device_memory_bytes <= V5E.hbm_per_chip
