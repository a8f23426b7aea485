"""A new architecture enters the benchmark as new files only, and the
reference's walk carries a layer's auxiliary loss into the gradients."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench_cases import BENCH, ROOT, cell_files, tiny
from yardstick import arch, reference
from yardstick.arch import dense

CELL = "toy-windows.train-s64.1chip"

# the dense block with a window per layer, as the program's
# `window_pattern` runs it
TOY_ARCH = '''"""Dense block with each layer's window from `window_pattern`."""
from yardstick import flops
from yardstick.arch import dense

layout = dense.layout
layer_leaves = dense.layer_leaves
matmul_flops_per_token = dense.matmul_flops_per_token


def windows(cfg):
    return list(cfg["window_pattern"])


def make_layer(cfg, ops):
    return dense.make_layer(cfg, ops, windows)


def attention_keys(cfg, seq):
    return [flops.mean_keys(seq, w) for w in windows(cfg)]


def tiny(cfg):
    return dict(dense.tiny(cfg), window_pattern=[16, 0])
'''

TOY_TRAFFIC = {"kind": "train", "batch": 1, "seq": 64, "mesh": None,
               "ring": 4, "warmup_steps": 2, "compare_steps": 3,
               "steps": 1000000, "v_eff": 256, "structure": 0.8}

# the toy's own limits, for its tiny size on the CPU: about three times
# the largest readings of its runs on seeds 1-12 and 2**31 + 21 (loss
# 3.2e-4, gnorm 1.5e-3, grad0 2.1e-3, update 1.5e-3)
TOY_LIMITS = {"loss_gap": 1e-3, "gnorm_gap": 5e-3, "grad0_gap": 6e-3,
              "update_gap": 5e-3}

PROBE = r'''
import argparse, importlib, json, sys, time
from pathlib import Path
sys.path[:0] = [str(Path.cwd() / "perfbench"), {src!r}]
import jax
import run as harness
bench, cell, cfg, traffic = harness.find_cell({cell!r})
from yardstick import arch, compare, flops, weights
mod = arch.module(cfg)
small = mod.tiny(cfg)
driver = importlib.import_module("drivers." + traffic["kind"])
args = argparse.Namespace(seed={seed}, seconds=0.3, trace=0)
out = driver.run(cell, small, traffic, args, jax.devices(), time.perf_counter(),
                 compare.load_limits(harness.HERE, cell["name"]))
print(json.dumps({{
    "files": [harness.__file__, mod.__file__, flops.__file__, driver.__file__],
    "e2e": [m["name"] for m in harness.metrics_of(bench, cell, False)],
    "per_layer": [m["name"] for m in harness.metrics_of(bench, cell, True)],
    "readers": [harness.reader(m["name"]).__module__
                for m in harness.metrics_of(bench, cell, True)],
    "layout": sorted(weights.layout(small)), "tiny": small,
    "flops": flops.train_flops_per_token(cfg, traffic["seq"]),
    "correct": out["correct"], "checks": out["checks"]}}))
'''


def _sums(root):
    return {p.relative_to(root).as_posix(): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def add_toy_cell(dst):
    """Copy the benchmark to `dst` and add a cell of a new architecture:
    new files, and one entry each under `configs` and `workloads`."""
    shutil.copy(ROOT / "BENCHMARK.json", dst)
    shutil.copytree(BENCH, dst / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    pb = dst / "perfbench"
    (pb / "yardstick" / "arch" / "dense_windows.py").write_text(TOY_ARCH)
    cfg, _ = cell_files("chatglm3-6b.train-s2k.1chip")
    cfg.update(name="toy-windows", arch="dense_windows", num_layers=2,
               d_model=256, num_heads=4, num_kv_heads=2, head_dim=64,
               d_ff=512, vocab_size=1024, rope="standard", rope_fraction=1.0,
               window=0, window_pattern=[32, 0])
    (pb / "configs" / "toy-windows.json").write_text(json.dumps(cfg))
    (pb / "traffic" / "train-s64.json").write_text(json.dumps(TOY_TRAFFIC))
    (pb / "limits" / f"{CELL}.json").write_text(json.dumps(
        {"limits": {k: {"limit": v} for k, v in TOY_LIMITS.items()}}))
    bench = json.loads((dst / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy-windows", "source": "test",
                             "file": "perfbench/configs/toy-windows.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "toy-windows",
                               "traffic": "train-s64", "chips": 1,
                               "why": "test"})
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    return cfg


def probe(dst, seed):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    code = PROBE.format(src=str(ROOT / "src"), cell=CELL, seed=seed)
    res = subprocess.run([sys.executable, "-c", code], cwd=dst, env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_new_architecture_needs_no_edit(tmp_path):
    cfg = add_toy_cell(tmp_path)
    out = probe(tmp_path, seed=2**31 + 21)
    # every part resolved through the copy
    assert all(f.startswith(str(tmp_path)) for f in out["files"]), out["files"]
    # and no file the benchmark had was edited but its cell list
    before, after = _sums(BENCH), _sums(tmp_path / "perfbench")
    assert {k: after[k] for k in before} == before
    assert "setup_s" in out["e2e"] and len(out["e2e"]) >= 2
    assert out["per_layer"] and all(out["readers"])
    assert out["layout"] == sorted(dense.layout(tiny()))
    assert out["tiny"]["window_pattern"] == [16, 0]
    assert out["tiny"]["d_model"] == 64 and out["tiny"]["num_layers"] == 2
    # the FLOP count takes each layer's window: 32 keys, then all 64
    keys = (32 * 33 / 2 + 32 * 32) / 64 + 65 / 2
    want = 3 * (2 * dense.matmul_weights(cfg) + 4 * 4 * 64 * keys)
    assert out["flops"] == pytest.approx(want, rel=1e-12)
    assert out["correct"], out["checks"]


def _toy_with_aux(coef):
    """The dense block whose layer l adds (l + 1) * mean(x_out^2) as its
    auxiliary loss; the configuration weighs it by `coef`."""
    def make_layer(cfg, ops):
        layer = dense.make_layer(cfg, ops)

        def with_aux(p, x, l):
            y, _ = layer(p, x, l)
            return y, (l + 1) * jnp.mean(y * y)
        return with_aux

    toy = SimpleNamespace(**{k: getattr(dense, k) for k in (
        "layout", "layer_leaves", "matmul_flops_per_token", "attention_keys",
        "tiny")})
    toy.make_layer = make_layer
    return toy, dict(tiny(compute_dtype="float32"), router_aux_coef=coef)


def _whole_loss(cfg, ops, p, tokens):
    """The toy's loss over the whole batch in one function."""
    L = cfg["num_layers"]
    B, S = tokens.shape
    layer = _toy_with_aux(0.0)[0].make_layer(cfg, ops)
    nll = aux = 0.0
    for b in range(B):
        x = p["embed/in_table"][tokens[b]]
        for l in range(L):
            x, a = layer({n: p[f"layers/{n}/{l}"] for n in dense.LAYER_LEAVES},
                         x, l)
            aux = aux + a
        lg = ops.mm("sd,dv->sv", ops.norm(x, p["final_norm/scale"]),
                    p["embed/out_head"])
        lp = jax.nn.log_softmax(lg[:-1], -1)
        nll = nll - jnp.sum(jnp.take_along_axis(lp, tokens[b, 1:, None], -1))
    return nll / (B * (S - 1)) + cfg["router_aux_coef"] / L * aux / B


@pytest.mark.parametrize("coef", [0.0, 0.5])
def test_walk_adds_the_layers_aux(monkeypatch, coef):
    """The walk's loss and gradients are `jax.grad` of the toy written
    whole, with its aux term; a weight of 0 leaves the aux out."""
    toy, cfg = _toy_with_aux(coef)
    monkeypatch.setattr(arch, "module", lambda c: toy)
    opt = dict(cfg["optimizer"], total_steps=100)
    ref = reference.Reference(cfg, opt)
    ref.init(11)
    toks = np.random.default_rng(11).integers(0, 512, (2, 64), np.int32)
    got = {}

    def path(key):
        name, l = key
        return name if l is None else f"layers/{name}/{l}"

    loss = ref._walk(toks, lambda key, g: got.update({path(key): g}))
    p = {path(key): v for key, v in ref.p.items()}
    ops = reference.make_ops()
    want_loss, want = jax.value_and_grad(
        lambda q: _whole_loss(cfg, ops, q, jnp.asarray(toks)))(p)
    assert loss == pytest.approx(float(want_loss), rel=1e-6)
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=2e-4, atol=1e-7,
                                   err_msg=k)
    if coef:
        # the aux term moves the gradients by far more than the tolerance
        plain = jax.grad(lambda q: _whole_loss(
            dict(cfg, router_aux_coef=0.0), ops, q, jnp.asarray(toks)))(p)
        assert max(float(jnp.max(jnp.abs(plain[k] - want[k])))
                   / float(jnp.max(jnp.abs(want[k]))) for k in want) > 0.05
