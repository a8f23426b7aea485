"""A run with the timed path broken underneath comes out not correct.

Each test skips only the harness's look for a chip: it drives the rest of
a train cell's run (set-up, `Trainer.run` through the window, the plain
reference, the checks against the cell's limits) at a tiny size on the
CPU, with one fault planted in the program.
"""
import argparse
import importlib
import time

import jax
import pytest

from bench_cases import BENCH, DATA, bench_json, tiny, tiny_traffic
from yardstick import compare

CELLS = [c["name"] for c in bench_json()["workloads"]]


def run_cell(cell, seed=2**31 + 3, batch=1):
    """A run of the cell at its tiny size, through the driver its traffic
    file names."""
    limits = compare.load_limits(BENCH, cell)
    assert limits, f"no limits for {cell}"
    traffic = tiny_traffic(cell, batch=batch)
    driver = importlib.import_module(f"drivers.{traffic['kind']}")
    args = argparse.Namespace(seed=seed, seconds=0.3, trace=0)
    return driver.run({"name": cell, "chips": 1}, tiny(cell), traffic, args,
                      jax.devices(), time.perf_counter(), limits)


def gaps(out):
    return {k: c["value"] for k, c in out["checks"].items()}


@pytest.fixture(scope="module", params=CELLS)
def cell(request):
    return request.param


@pytest.fixture(scope="module")
def sound(cell):
    return gaps(run_cell(cell))


def test_untraced_run_reads_no_hlo(monkeypatch, cell):
    """Only traced runs read the executable's HLO text for the scope map."""
    def no_text(self, *a, **k):
        raise AssertionError("as_text() in an untraced run")

    monkeypatch.setattr(jax.stages.Compiled, "as_text", no_text)
    assert gaps(run_cell(cell))


def test_state_returned_unchanged(monkeypatch, cell, sound):
    import repro.launch.train as train_mod
    real = train_mod.make_train_step

    def frozen(cfg, opt_cfg, st):
        step = real(cfg, opt_cfg, st)

        def broken(params, opt_state, batch):
            _, _, metrics = step(params, opt_state, batch)
            return params, opt_state, metrics
        return broken

    monkeypatch.setattr(train_mod, "make_train_step", frozen)
    out = run_cell(cell)
    assert not out["correct"]
    assert gaps(out)["update_gap"] == pytest.approx(1.0, abs=1e-3)
    assert gaps(out)["grad0_gap"] == pytest.approx(1.0, abs=1e-3)
    assert sound["update_gap"] < 0.01 and sound["grad0_gap"] < 0.01


@pytest.mark.parametrize("batch", [1, 2])
def test_half_the_batch_left_out(monkeypatch, cell, sound, batch):
    """The loss's mean taken over half of the rows (half of the positions
    of a one-row batch)."""
    from repro.models import api
    real = api.loss_fn

    def half(cfg, params, b, **kw):
        t = b["tokens"]
        t = t[: t.shape[0] // 2] if t.shape[0] > 1 else t[:, : t.shape[1] // 2]
        return real(cfg, params, dict(b, tokens=t), **kw)

    monkeypatch.setattr(api, "loss_fn", half)
    out = run_cell(cell, batch=batch)
    assert not out["correct"]
    assert gaps(out)["gnorm_gap"] > 0.05 > 20 * sound["gnorm_gap"]


NO_EXCHANGE = r'''
import argparse, json, sys, time
sys.path[:0] = [{tests!r}]
import jax, jax.numpy as jnp
from bench_cases import BENCH, tiny, tiny_traffic
from drivers import train
from yardstick import compare
import repro.launch.train as train_mod
from repro.distributed import sharding as shlib
from repro.models import api
from repro.optim import adamw

held = {{}}
real_pspecs = shlib.param_pspecs


def spy(cfg, mesh, *a, **k):
    held["pspecs"] = real_pspecs(cfg, mesh, *a, **k)
    return held["pspecs"]


def no_exchange(cfg, opt_cfg, st):
    """Each half of the data axis updates its slices of the params from
    its own rows only: the gradient all-reduce over `data` left out."""
    def loss(p, toks):
        return api.loss_fn(cfg, p, {{"tokens": toks}}, attn_impl=st.attn_impl,
                           remat=st.remat)

    def step(params, opt_state, batch):
        t = batch["tokens"]
        half = t.shape[0] // 2
        la, ga = jax.value_and_grad(loss)(params, t[:half])
        _, gb = jax.value_and_grad(loss)(params, t[half:])

        def mix(a, b, spec):
            for d, ax in enumerate(tuple(spec)):
                axes = ax if isinstance(ax, tuple) else (ax,)
                if "data" in axes:
                    n = a.shape[d] // 2
                    return jnp.concatenate(
                        [jax.lax.slice_in_dim(a, 0, n, axis=d),
                         jax.lax.slice_in_dim(b, n, a.shape[d], axis=d)], d)
            return a
        grads = jax.tree.map(mix, ga, gb, held["pspecs"],
                             is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
        p, o, m = adamw.update(opt_cfg, grads, opt_state, params)
        m["loss"] = la
        return p, o, m
    return step


shlib.param_pspecs = spy
train_mod.make_train_step = no_exchange
cell = "chatglm3-6b.train-s2k.1chip"
args = argparse.Namespace(seed=2**31 + 3, seconds=0.3, trace=0)
mesh = {{"shape": [2, 2], "axes": ["data", "model"]}}
out = train.run({{"name": cell, "chips": 4}}, tiny(cell),
                tiny_traffic(cell, batch=2, mesh=mesh), args, jax.devices(), time.perf_counter(),
                compare.load_limits(BENCH, cell))
print(json.dumps({{"correct": out["correct"],
                  "gaps": {{k: c["value"] for k, c in out["checks"].items()}}}}))
'''


def test_exchange_between_chips_left_out():
    """2x2 (data, model) on four CPU devices, grad sync over data left out."""
    import json
    import os
    import subprocess
    import sys
    code = NO_EXCHANGE.format(tests=str(DATA.parent))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert not out["correct"]
    assert max(out["gaps"]["gnorm_gap"], out["gaps"]["grad0_gap"]) > 0.05
