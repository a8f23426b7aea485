"""Training-loop integration: convergence, bitwise resume, crash recovery,
straggler watchdog, optimizer correctness."""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
from _hypothesis_compat import given, settings, strategies as st

from repro.configs import ARCHS, smoke_config
from repro.data import DataConfig, SyntheticTokens
from repro.launch.presets import StepSettings
from repro.launch.train import Trainer
from repro.optim import AdamWConfig, adamw
from repro.training.watchdog import StragglerWatchdog

CFG = smoke_config(ARCHS["h2o-danube-3-4b"])


def make_trainer(tmp, **kw):
    kw.setdefault("steps", 8)
    kw.setdefault("batch", 2)
    kw.setdefault("seq", 64)
    kw.setdefault("ckpt_every", 4)
    return Trainer(CFG, ckpt_dir=str(tmp), **kw)


def test_loss_decreases(tmp_path):
    tr = make_trainer(tmp_path, steps=15, ckpt_every=0)
    log = tr.run()
    first = np.mean([m["loss"] for m in log[:3]])
    last = np.mean([m["loss"] for m in log[-3:]])
    assert last < first - 0.05, (first, last)


def test_resume_bitwise(tmp_path):
    """6 straight steps == 4 steps + restore + 2 steps (same data, params)."""
    a = make_trainer(tmp_path / "a", steps=6, ckpt_every=10)
    log_a = a.run()

    b1 = make_trainer(tmp_path / "b", steps=4, ckpt_every=4)
    b1.run()
    b2 = make_trainer(tmp_path / "b", steps=6, ckpt_every=4)
    log_b = b2.run()

    assert len(log_b) == 2   # resumed at step 4
    la = [m["loss"] for m in log_a[-2:]]
    lb = [m["loss"] for m in log_b]
    np.testing.assert_allclose(la, lb, rtol=0, atol=0)   # bitwise


def test_crash_injection_and_recovery(tmp_path):
    """Hard-crash at step 4 (exit 42), restart completes the run."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    args = [sys.executable, "-m", "repro.launch.train", "--arch",
            "h2o-danube-3-4b", "--smoke", "--steps", "8", "--batch", "2",
            "--seq", "64", "--ckpt-dir", str(tmp_path), "--ckpt-every", "2"]
    res1 = subprocess.run(args + ["--fail-at-step", "4"], env=env,
                          capture_output=True, text=True, timeout=560)
    assert res1.returncode == 42
    assert "injected failure" in res1.stdout
    res2 = subprocess.run(args, env=env, capture_output=True, text=True,
                          timeout=560)
    assert res2.returncode == 0, res2.stderr[-2000:]
    assert "resumed from checkpoint at step 4" in res2.stdout
    assert "done" in res2.stdout


def test_watchdog_flags_stragglers():
    wd = StragglerWatchdog(window=50, sigma=4.0)
    for i in range(30):
        wd.observe(i, 0.100 + 0.001 * (i % 3))
    st_ = wd.observe(31, 0.5)      # 5x slower
    assert st_.flagged
    st2 = wd.observe(32, 0.101)
    assert not st2.flagged
    assert wd.hang_deadline_s() >= 0.5


def test_adamw_matches_reference():
    """One AdamW step against a hand-computed reference."""
    cfg = AdamWConfig(lr=0.1, beta1=0.9, beta2=0.99, eps=1e-8,
                      weight_decay=0.0, clip_norm=0.0, warmup_steps=0,
                      total_steps=10**9, min_lr_ratio=1.0)
    p = {"w": jnp.asarray([1.0, -2.0])}
    g = {"w": jnp.asarray([0.5, 0.5])}
    state = adamw.init(cfg, p)
    new_p, new_state, _ = adamw.update(cfg, g, state, p)
    m = 0.1 * 0.5
    v = 0.01 * 0.25
    mhat = m / (1 - 0.9)
    vhat = v / (1 - 0.99)
    step = mhat / (np.sqrt(vhat) + 1e-8)
    np.testing.assert_allclose(np.asarray(new_p["w"]),
                               np.asarray(p["w"]) - 0.1 * step, rtol=1e-5)


@given(step=st.integers(0, 10_000))
@settings(max_examples=30, deadline=None)
def test_schedule_bounds(step):
    cfg = AdamWConfig(lr=3e-4, warmup_steps=100, total_steps=10_000)
    lr = float(adamw.schedule(cfg, jnp.asarray(step)))
    assert 0.0 <= lr <= cfg.lr * (1 + 1e-6)
    if step >= cfg.total_steps:
        assert lr <= cfg.lr * cfg.min_lr_ratio * (1 + 1e-4) + 1e-9


def test_data_determinism_and_seek():
    data = SyntheticTokens(CFG, DataConfig(4, 32, seed=7))
    b1 = data.batch_at(10)
    b2 = data.batch_at(10)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = data.batch_at(11)
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    it = data.iter_from(10)
    np.testing.assert_array_equal(next(it)["tokens"], b1["tokens"])
    assert b1["tokens"].min() >= 0
    assert b1["tokens"].max() < CFG.vocab_size


def test_grad_compression_still_trains(tmp_path):
    tr = Trainer(CFG, steps=6, batch=2, seq=64, ckpt_dir=None, ckpt_every=0,
                 settings=StepSettings(accum=1, remat="dots",
                                       grad_compression="bf16"))
    log = tr.run()
    assert np.isfinite([m["loss"] for m in log]).all()


def test_sharded_trainer_builds_state_on_the_mesh(subproc):
    """A `Trainer(mesh=...)` builds params and optimizer state already
    sharded (no device holds the whole model), with the values of the
    unsharded init, and trains to the same losses as one device."""
    out = subproc("""
import jax
import numpy as np
from repro.configs import ARCHS, smoke_config
from repro.launch.mesh import make_host_mesh
from repro.launch.train import Trainer
from repro.models import api

cfg = smoke_config(ARCHS["chatglm3-6b"])
mesh, _ = make_host_mesh((2, 2), ("data", "model"))
kw = dict(steps=3, batch=2, seq=32, ckpt_dir=None, ckpt_every=0)
tr = Trainer(cfg, mesh=mesh, **kw)
params, opt, _ = tr.init_state(0)
state = jax.tree.leaves((params, opt))
want = jax.tree.leaves((tr.param_sh, tr.opt_sh))
assert len(state) == len(want)
for leaf, sh in zip(state, want):
    assert leaf.sharding.is_equivalent_to(sh, leaf.ndim), (leaf.sharding, sh)
assert any(not leaf.sharding.is_fully_replicated for leaf in state)
for got, ref in zip(jax.tree.leaves(params),
                    jax.tree.leaves(api.init_params(cfg, 0))):
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-6)
del params, opt, state
sharded = [(m["loss"], m["grad_norm"]) for m in tr.run(0)]
single = [(m["loss"], m["grad_norm"]) for m in Trainer(cfg, **kw).run(0)]
# bf16 activations (rounding 2^-9) reduced in another order over the mesh
np.testing.assert_allclose(sharded, single, rtol=1e-2)
print("OK")
""", devices=4)
    assert "OK" in out


def _host_spans(trace_dir):
    """[name, start_ns, end_ns, step_num] of the `train.*` host events."""
    import glob

    import jax
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [[e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats).get("step_num")]
                        for e in line.events if e.name.startswith("train.")]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def test_run_spans_tile_each_step(tmp_path):
    """Under a profiler trace each step is one `train.step` with its
    `step_num`, holding data, dispatch, wait, fetch and log in that order;
    `train.ckpt` marks only the saves and `train.compile` the one compile."""
    import jax
    tr = make_trainer(tmp_path / "ck", steps=4, ckpt_every=2)
    with jax.profiler.trace(str(tmp_path / "trace")):
        tr.run()
    spans = _host_spans(str(tmp_path / "trace"))
    steps = [s for s in spans if s[0] == "train.step"]
    assert [s[3] for s in steps] == [0, 1, 2, 3]
    phases = ["train.data", "train.dispatch", "train.wait", "train.fetch",
              "train.log"]
    for name, lo, hi, _ in steps:
        inner = [s[0] for s in spans if lo <= s[1] and s[2] <= hi
                 and s[0] in phases]
        assert inner == phases
    compiles = [s for s in spans if s[0] == "train.compile"]
    assert len(compiles) == 1
    (dispatch0,) = [s for s in spans if s[0] == "train.dispatch"
                    and steps[0][1] <= s[1] < steps[0][2]]
    assert dispatch0[1] <= compiles[0][1] and compiles[0][2] <= dispatch0[2]
    ckpts = [s for s in spans if s[0] == "train.ckpt"]
    in_step = [next((st[3] for st in steps if st[1] <= c[1] < st[2]), None)
               for c in ckpts]
    # saves after steps 1 and 3 (next step 2 and 4), then the final save
    assert in_step == [1, 3, None]


def test_step_hlo_names_the_model_parts():
    """The compiled train step's op metadata carries the model's named
    scopes (under jax's transform wrappers), with the layers rematerialised."""
    import re

    tr = Trainer(CFG, steps=1, batch=2, seq=32, ckpt_dir=None, ckpt_every=0,
                 settings=StepSettings(accum=1, remat="full"))
    params, opt, _ = tr.init_state(0)
    batch = {k: jnp.asarray(v) for k, v in tr.data.batch_at(0).items()}
    text = tr.compile(params, opt, batch).as_text()

    def components(path):
        out = set()
        for c in path.split("/"):
            while (m := re.fullmatch(r"[\w.\-]+\((.*)\)", c)):
                c = m.group(1)
            out.add(c)
        return out

    found = set().union(*(components(p) for p in
                          re.findall(r'op_name="([^"]+)"', text)))
    assert {"attn", "embed", "optimizer", "layer"} <= found
    assert {"logits", "loss"} & found
    assert "checkpoint" in found or "rematted_computation" in found
