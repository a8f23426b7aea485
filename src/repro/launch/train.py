"""Fault-tolerant training driver.

Features exercised by the integration tests:
  * deterministic seekable data (restart reproduces batches bitwise),
  * periodic atomic checkpoints + resume from LATEST,
  * crash injection (`--fail-at-step`) for restart-continuity testing,
  * SIGTERM preemption handler (checkpoint then exit 0),
  * straggler watchdog with step-time stats,
  * optional mesh execution (`--mesh DxM`) over available devices,
  * profiler spans: each step is a `train.step` (its `step_num` gives
    xprof/TensorBoard their step markers) tiled by the loop's phases
    `train.data`, `train.dispatch`, `train.wait`, `train.fetch` and
    `train.log`; `train.ckpt` marks each checkpoint save and
    `train.compile` the step's one compile.  They record only while a
    profiler trace is running (`jax.profiler.trace`).

Run e.g.:
    PYTHONPATH=src python -m repro.launch.train --arch chatglm3-6b --smoke \
        --steps 50 --batch 4 --seq 128 --ckpt-dir /tmp/ck --ckpt-every 20
"""
from __future__ import annotations

import argparse
import os
import signal
import sys
import time

import jax
import jax.numpy as jnp
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro import checkpoint
from repro.configs import get_config, smoke_config
from repro.data import DataConfig, SyntheticTokens
from repro.distributed import sharding as shlib
from repro.distributed.autoshard import activation_sharding
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.launch.presets import StepSettings
from repro.launch.steps import make_train_step
from repro.models import api as model_api
from repro.optim import adamw
from repro.training.watchdog import StragglerWatchdog


class Trainer:
    def __init__(self, cfg, *, steps=100, batch=8, seq=256, ckpt_dir=None,
                 ckpt_every=50, mesh=None, settings=None, opt_cfg=None,
                 seed=0, fail_at_step=None, log_every=10, keep=3):
        self.cfg = cfg
        self.steps = steps
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = ckpt_every
        self.mesh = mesh
        self.fail_at_step = fail_at_step
        self.log_every = log_every
        self.keep = keep
        self.settings = settings or StepSettings(accum=1, remat="dots")
        self.opt_cfg = opt_cfg or adamw.AdamWConfig(
            lr=1e-3, warmup_steps=20, total_steps=steps,
            state_dtype=self.settings.opt_state_dtype)
        self.data = SyntheticTokens(cfg, DataConfig(batch, seq, seed=seed))
        self.watchdog = StragglerWatchdog()
        self.metrics_log = []
        self._preempted = False

        self.step_fn = make_train_step(cfg, self.opt_cfg, self.settings)
        # the executable every step runs, compiled ahead of the first one
        # (`compile`) so that profilers trace this very program
        self.compiled = None
        self.compile_s = None
        if mesh is not None:
            pspecs = shlib.param_pspecs(cfg, mesh)
            psh = shlib.named(mesh, pspecs)
            osh = shlib.named(mesh, {"m": pspecs, "v": pspecs,
                                     "count": jax.sharding.PartitionSpec()})
            self.jit_step = jax.jit(self.step_fn, donate_argnums=(0, 1),
                                    in_shardings=(psh, osh, None),
                                    out_shardings=(psh, osh, None))
            self.param_sh, self.opt_sh = psh, osh
        else:
            self.jit_step = jax.jit(self.step_fn, donate_argnums=(0, 1))
            self.param_sh = self.opt_sh = None

    # ---- state ------------------------------------------------------------
    def init_state(self, seed=0):
        if self.param_sh is None:
            params = model_api.init_params(self.cfg, seed)
            return params, adamw.init(self.opt_cfg, params), 0
        # built in place on the mesh: no device ever holds the whole model
        params = jax.jit(lambda: model_api.init_params(self.cfg, seed),
                         out_shardings=self.param_sh)()
        opt = jax.jit(lambda p: adamw.init(self.opt_cfg, p),
                      out_shardings=self.opt_sh)(params)
        return params, opt, 0

    def restore_or_init(self, seed=0):
        if self.ckpt_dir and checkpoint.latest_step(self.ckpt_dir) is not None:
            params, opt, _ = self.init_state(seed)
            tree = {"params": params, "opt": opt}
            sh = None
            if self.param_sh is not None:
                sh = {"params": self.param_sh, "opt": self.opt_sh}
            restored, extra = checkpoint.restore(self.ckpt_dir, tree,
                                                 shardings=sh)
            step = int(extra.get("next_step", 0))
            print(f"[train] resumed from checkpoint at step {step}")
            return restored["params"], restored["opt"], step
        return self.init_state(seed)

    def save_ckpt(self, params, opt, next_step):
        if not self.ckpt_dir:
            return
        with TraceAnnotation("train.ckpt"):
            checkpoint.save(self.ckpt_dir, next_step,
                            {"params": params, "opt": opt},
                            extra={"next_step": next_step,
                                   "arch": self.cfg.name})
            checkpoint.prune_old(self.ckpt_dir, keep=self.keep)

    def compile(self, params, opt, batch):
        """Lower + compile the step for these arguments (once)."""
        if self.compiled is None:
            with TraceAnnotation("train.compile"):
                t0 = time.perf_counter()
                self.compiled = self.jit_step.lower(params, opt, batch).compile()
                self.compile_s = time.perf_counter() - t0
        return self.compiled

    # ---- loop -------------------------------------------------------------
    def run(self, seed=0) -> list:
        params, opt, start = self.restore_or_init(seed)

        def on_sigterm(_sig, _frm):
            self._preempted = True
        old = signal.signal(signal.SIGTERM, on_sigterm)

        ctx = activation_sharding(self.mesh) if self.mesh is not None else None
        try:
            if ctx:
                ctx.__enter__()
            for step in range(start, self.steps):
                # the phases tile the step, so a device-idle gap in a
                # trace falls in the phase that kept the host busy
                with StepTraceAnnotation("train.step", step_num=step):
                    with TraceAnnotation("train.data"):
                        batch = {k: jnp.asarray(v)
                                 for k, v in self.data.batch_at(step).items()}
                    with TraceAnnotation("train.dispatch"):
                        step_fn = self.compile(params, opt, batch)
                        self.watchdog.start_step(step)
                        params, opt, metrics = step_fn(params, opt, batch)
                    with TraceAnnotation("train.wait"):
                        jax.block_until_ready((params, opt, metrics))
                    with TraceAnnotation("train.fetch"):
                        loss = float(metrics["loss"])
                        gnorm = float(metrics["grad_norm"])
                    with TraceAnnotation("train.log"):
                        st = self.watchdog.end_step()
                        self.metrics_log.append(
                            {"step": step, "loss": loss, "grad_norm": gnorm,
                             "sec": st.duration_s, "straggler": st.flagged})
                        if step % self.log_every == 0 or step == self.steps - 1:
                            print(f"[train] step {step:5d} loss {loss:.4f} "
                                  f"gnorm {gnorm:.3f} "
                                  f"({st.duration_s*1e3:.0f} ms)")
                    next_step = step + 1
                    if self.ckpt_every and next_step % self.ckpt_every == 0:
                        self.save_ckpt(params, opt, next_step)
                    if self._preempted:
                        print("[train] SIGTERM: checkpointing and exiting")
                        self.save_ckpt(params, opt, next_step)
                        sys.exit(0)
                    if self.fail_at_step is not None and next_step == self.fail_at_step:
                        print(f"[train] injected failure at step {next_step}",
                              flush=True)
                        os._exit(42)   # simulate a hard node crash
            self.save_ckpt(params, opt, self.steps)
        finally:
            if ctx:
                ctx.__exit__(None, None, None)
            signal.signal(signal.SIGTERM, old)
        return self.metrics_log


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config for CPU")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-at-step", type=int, default=None)
    ap.add_argument("--mesh", default=None, help="DxM over available devices")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--accum", type=int, default=1)
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    mesh = None
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_mesh((d, m), ("data", "model"))
    tr = Trainer(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
                 ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                 mesh=mesh, fail_at_step=args.fail_at_step,
                 settings=StepSettings(accum=args.accum, remat="dots"))
    log = tr.run(args.seed)
    losses = [m["loss"] for m in log]
    if losses:
        print(f"[train] done: loss {losses[0]:.4f} -> {losses[-1]:.4f} "
              f"({len(losses)} steps)")


if __name__ == "__main__":
    main()
