"""Train cells: `launch.train.Trainer.run` fed from the device.

Set-up makes the weights on the device from the seed, and a ring of
distinct token batches from the seed, placed with the step's input
sharding.  `Trainer.run` is then called once with a step count no window
reaches; its data object hands back ring entries that are already on the
device, so `jnp.asarray` there is a no-op.  After `warmup_steps` steps
the window opens at the next data call, and the first data call that
finds `--seconds` passed closes it by raising `WindowClosed`, which the
harness catches outside `run` (whose `finally` restores its state).

The loop is closed: each step waits for the last (`Trainer.run` blocks on
every step).  A step interval runs from one data call to the next and
holds the step, its sync and the loop's host work.

`correct` compares the first `compare_steps` steps of this very run (its
losses and grad norms, its first moment after one step, its params after
`compare_steps` steps) with the plain reference, run once the window has
closed and the trainer's state is freed.
"""
from __future__ import annotations

import gc
import math
import shutil
import tempfile
import time
from types import SimpleNamespace
from typing import Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp

from yardstick import compare, flops, reference, tokens, trace, weights


class WindowClosed(Exception):
    """Raised by the data object when the measured window is over."""


class RingData:
    """`Trainer.data` stand-in: step k gets ring entry k mod len(ring)."""

    def __init__(self, ring, warmup: int, seconds: float,
                 on_open: Callable = lambda: None,
                 on_close: Callable = lambda: None):
        self.ring, self.warmup, self.seconds = ring, warmup, seconds
        self.on_open, self.on_close = on_open, on_close
        self.stamps = []
        self.t_open = self.t_close = None

    def batch_at(self, step: int):
        with jax.profiler.TraceAnnotation("bench.batch_at"):
            if step == self.warmup:
                self.on_open()
                self.t_open = time.perf_counter()
            elif step > self.warmup:
                now = time.perf_counter()
                if now - self.t_open >= self.seconds:
                    self.t_close = now
                    self.on_close()
                    raise WindowClosed
                self.stamps.append(now)
            if step == self.warmup:
                self.stamps.append(self.t_open)
            return self.ring[step % len(self.ring)]



def window_metrics(stamps, t_close: float, tokens_per_step: int) -> dict:
    """From the data-call stamps of the window's steps (the first is the
    window's start) and the closing call's: the steps completed, the
    tokens per second over the whole window, and the 90th percentile of
    the step intervals (linear interpolation)."""
    iv = np.diff(np.asarray(list(stamps) + [t_close], np.float64))
    span = t_close - stamps[0]
    return {"steps": len(stamps), "seconds": span,
            "tokens_per_s": len(stamps) * tokens_per_step / span,
            "step_ms_p90": float(np.percentile(iv, 90)) * 1e3}


class GcSpans:
    """Times the interpreter's garbage collections in the window and
    marks each in the trace as a `bench.gc` span."""

    def __init__(self):
        self.pauses = []
        self._open = None

    def __call__(self, phase, info):
        if phase == "start":
            self._open = (time.perf_counter(), jax.profiler.TraceAnnotation(
                f"bench.gc{info['generation']}"))
            self._open[1].__enter__()
        elif self._open is not None:
            self._open[1].__exit__(None, None, None)
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._open[0]))
            self._open = None

    def start(self):
        gc.callbacks.append(self)

    def stop(self):
        if self in gc.callbacks:
            gc.callbacks.remove(self)

    def note(self) -> str:
        by = {}
        for g, s in self.pauses:
            n, tot, top = by.get(g, (0, 0.0, 0.0))
            by[g] = (n + 1, tot + s, max(top, s))
        return "gc in window (generation: count, total s, longest s): " + (
            ", ".join(f"{g}: {n}, {tot}, {top}" for g, (n, tot, top)
                      in sorted(by.items())) or "none")


def model_config(cfg: dict):
    from dataclasses import fields

    from repro.configs.base import ModelConfig

    names = {f.name for f in fields(ModelConfig)}
    kw = {k: tuple(v) if isinstance(v, list) else v
          for k, v in cfg.items() if k in names}
    return ModelConfig(**kw)


def _paths(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(k.key for k in path): x for path, x in flat}


def _leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for k, x in _paths(tree).items()}


def _host(tree) -> dict:
    return {k: float(v) for k, v in jax.device_get(tree).items()}


def build(cfg: dict, traffic: dict, seed: int, devices, *, warmup: int,
          seconds: float, on_open=lambda: None, on_close=lambda: None):
    """The trainer of this cell, its state and its data, ready to `run`."""
    from repro.distributed import sharding as shlib
    from repro.configs.base import ShapeSpec
    from repro.launch import train as train_mod
    from repro.launch.mesh import make_mesh
    from repro.launch.presets import StepSettings
    from repro.models import api as model_api
    from repro.optim import adamw

    mcfg = model_config(cfg)
    B, S = traffic["batch"], traffic["seq"]
    opt = dict(cfg["optimizer"], total_steps=traffic["steps"])
    mesh = None
    if traffic.get("mesh"):
        shape, axes = traffic["mesh"]["shape"], traffic["mesh"]["axes"]
        n = int(np.prod(shape))
        mesh = make_mesh(shape, axes, devices=devices[:n])

    # the benchmark's weights are the program's tree, leaf for leaf
    kd = jnp.asarray(weights.key_data(seed))
    want = jax.tree.map(lambda s: (s.shape, str(s.dtype)),
                        model_api.abstract_params(mcfg))
    got = jax.tree.map(lambda s: (s.shape, str(s.dtype)), jax.eval_shape(
        lambda k: weights.nest(weights.flat(cfg, k)), kd))
    if want != got:
        raise ValueError("benchmark weight layout differs from the model's")

    probes = {}
    compare_steps = traffic["compare_steps"]
    norms = jax.jit(_leaf_norms)
    change = None

    class BenchTrainer(train_mod.Trainer):
        """`Trainer` with the benchmark's weights, read at two steps: its
        first moment after one step, its params after `compare_steps`."""
        calls = -1

        def init_state(self, seed=0):
            params = weights.make_params(cfg, seed, self.param_sh
                                         or jax.sharding.SingleDeviceSharding(
                                             devices[0]))
            opt_state = jax.jit(lambda p: adamw.init(self.opt_cfg, p),
                                out_shardings=self.opt_sh)(params)
            return params, opt_state, 0

        def compile(self, params, opt_state, batch):
            self.calls += 1
            if self.calls == 1:
                with jax.profiler.TraceAnnotation("bench.probe"):
                    probes["m1"] = _host(norms(opt_state["m"]))
            if self.calls == compare_steps:
                with jax.profiler.TraceAnnotation("bench.probe"):
                    probes["change"] = _host(change(params, kd))
            return super().compile(params, opt_state, batch)

    tr = BenchTrainer(mcfg, steps=traffic["steps"], batch=B, seq=S,
                      ckpt_dir=None, ckpt_every=0, mesh=mesh,
                      settings=StepSettings(**cfg["settings"]), seed=seed,
                      log_every=10**9, opt_cfg=adamw.AdamWConfig(**opt))
    shard = _paths(tr.param_sh) if tr.param_sh is not None else None

    def leaf_change(p, k):
        """Norm of each leaf's change since the weights of key data `k`."""
        x0 = weights.flat(cfg, k)
        if shard is not None:
            x0 = {n: jax.lax.with_sharding_constraint(x, shard[n])
                  for n, x in x0.items()}
        return {n: jnp.sqrt(jnp.sum(jnp.square(x - x0[n])))
                for n, x in _paths(p).items()}

    change = jax.jit(leaf_change)

    ring_np = tokens.token_ring(seed, traffic["ring"], B, S,
                                v_eff=traffic["v_eff"],
                                structure=traffic["structure"])
    if mesh is not None:
        bsh = shlib.named(mesh, shlib.batch_pspecs(
            mcfg, ShapeSpec("bench", "train", S, B), mesh))["tokens"]
    else:
        bsh = jax.sharding.SingleDeviceSharding(devices[0])
    ring = [{"tokens": jax.device_put(r, bsh)} for r in ring_np]
    jax.block_until_ready(ring)
    tr.data = RingData(ring, warmup, seconds, on_open, on_close)
    return SimpleNamespace(trainer=tr, ring_np=ring_np, probes=probes,
                           opt=opt, B=B, S=S)


def drive(b, seed: int):
    """`Trainer.run` until the data object closes the window."""
    try:
        with jax.profiler.TraceAnnotation("bench.run"):
            b.trainer.run(seed)
    except WindowClosed:
        return
    raise RuntimeError("Trainer.run ended before the window closed")


def program_readings(b, n: int) -> dict:
    """The first `n` steps as the timed path produced them."""
    log = b.trainer.metrics_log[:n]
    beta1 = b.opt["beta1"]
    return {"loss": [m["loss"] for m in log],
            "grad_norm": [m["grad_norm"] for m in log],
            "grad0": {k: v / (1 - beta1) for k, v in b.probes["m1"].items()},
            "change": b.probes["change"]}


def run(cell: dict, cfg: dict, traffic: dict, args, devices, t_start: float,
        limits: Optional[dict]):
    """One run of a train cell.  Returns the harness's result fields."""
    warmup, chips = traffic["warmup_steps"], cell["chips"]
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    spans = {}

    gcs = GcSpans()

    def on_open():
        spans["warmup"].__exit__(None, None, None)
        gc.collect()                    # set-up's garbage, before the window
        if tdir:
            jax.profiler.start_trace(tdir)
        spans["window"] = jax.profiler.TraceAnnotation("bench.window")
        spans["window"].__enter__()
        gcs.start()

    def on_close():
        gcs.stop()
        spans["window"].__exit__(None, None, None)

    spans["warmup"] = jax.profiler.TraceAnnotation("bench.warmup")
    spans["warmup"].__enter__()
    t_build = time.perf_counter()
    b = build(cfg, traffic, args.seed, devices, warmup=warmup,
              seconds=args.seconds, on_open=on_open, on_close=on_close)
    t_built = time.perf_counter()
    data = b.trainer.data
    try:
        drive(b, args.seed)
    finally:
        if tdir:
            jax.profiler.stop_trace()
    used = devices[:chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)

    n_cmp = traffic["compare_steps"]
    prog = program_readings(b, n_cmp)
    window_log = b.trainer.metrics_log[warmup:]
    failed = sum(not (math.isfinite(m["loss"]) and math.isfinite(m["grad_norm"]))
                 for m in b.trainer.metrics_log)
    w = window_metrics(data.stamps, data.t_close, b.B * b.S)
    n_steps, span_s, tok_s = w["steps"], w["seconds"], w["tokens_per_s"]
    e2e = {"tokens_per_s": tok_s, "step_ms_p90": w["step_ms_p90"],
           "setup_s": data.t_open - t_start}
    iv = np.diff(np.asarray(data.stamps + [data.t_close])) * 1e3
    slow = np.argsort(iv)[::-1][:3]
    notes = [
        f"setup phases (s): imports and backend {t_build - t_start}, "
        f"weights + ring + trainer {t_built - t_build}, step compile "
        f"{b.trainer.compile_s}, warm-up steps and probes "
        f"{data.t_open - t_built - b.trainer.compile_s}",
        f"window: {n_steps} steps in {span_s} s; step_ms_p90 over {n_steps} "
        f"step intervals; ms min {iv.min()} median {np.median(iv)} max "
        f"{iv.max()}; slowest at window steps {slow.tolist()}, of which "
        f"ms inside the step (dispatch to loss on the host) "
        f"{[window_log[i]['sec'] * 1e3 for i in slow if i < len(window_log)]}",
        f"warm-up step ms {[round(m['sec'] * 1e3, 3) for m in b.trainer.metrics_log[:warmup]]}",
        gcs.note()]
    # traced runs only: the executable's op-name paths, for the scope readers
    scopes = trace.scope_map(b.trainer.compiled.as_text()) if tdir else None
    ring_np = b.ring_np
    del b, data
    gc.collect()

    ref = reference.readings(cfg, dict(cfg["optimizer"],
                                       total_steps=traffic["steps"]),
                             args.seed, list(ring_np[:n_cmp]),
                             device=devices[0])
    gaps = compare.train_gaps(prog, ref)
    correct, checks = compare.judge(gaps, limits)

    out = {"correct": correct and failed == 0 and len(window_log) == n_steps,
           "attempted": n_steps, "failed": failed, "e2e": e2e,
           "checks": checks, "peak": peak, "chips": chips, "notes": notes}
    if tdir:
        rec = trace.load(tdir)
        rec["scopes"] = scopes
        shutil.rmtree(tdir, ignore_errors=True)
        lo, hi = trace.window(rec)
        busy = trace.busy_ns(rec, lo, hi)
        if not busy:
            raise RuntimeError("the trace holds no device operations")
        # what each reader in `metrics/` is given: the trace record with
        # its scope map, the window's bounds (ns), device busy ns per chip,
        # the window's steps and their `metrics_log` entries, and the cell
        out["trace"] = SimpleNamespace(
            rec=rec, lo=lo, hi=hi, busy=busy, n_steps=n_steps, log=window_log,
            tokens_per_s=tok_s, chips=chips, cfg=cfg, seq=traffic["seq"],
            device_kind=used[0].device_kind,
            flops_per_token=flops.train_flops_per_token(cfg, traffic["seq"]))
        out["busy_s"] = sum(busy.values()) / len(busy) * 1e-9
        out["window_s"] = (hi - lo) * 1e-9
        out["breakdown"] = {"device_ops": trace.top_ops(rec, lo, hi),
                            "idle_gaps": trace.idle_gaps(rec, lo, hi)}
    return out
