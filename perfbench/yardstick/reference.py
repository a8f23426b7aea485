"""Plain reference of the training step: forward, loss, gradients, AdamW.

Written from the configuration alone, in float32 with every matrix product
at `Precision.HIGHEST`; it imports nothing of the program and takes
nothing the program made.  It runs one batch row and one layer at a time,
so that it fits one chip beside nothing else:

  * the forward keeps only each layer's input row ([S, D]);
  * the backward of a layer is `jax.vjp` of that layer alone, recomputed;
  * attention is exact softmax over all keys, a block of queries at a
    time; the LM head and cross-entropy go a block of positions at a time.

Global-norm clipping needs every gradient before the first update, so each
step walks the backward twice: once to sum the squares, once to update
each leaf as soon as its gradient is complete.

Model, as the configuration states: x = table[tokens]; each layer is the
architecture module's (`arch/<arch>.py`, `make_layer`), built from the
helpers of `make_ops`: RMSNorm (eps 1e-6); causal GQA attention (query
head h reads key head h // (H/K)), scores scaled by head_dim**-0.5, keys
further back than a window masked when it is > 0; rope on the first
`fraction` of each head as two halves (x1, x2) -> (x1 cos - x2 sin,
x2 cos + x1 sin) at angles pos * theta**(-i/n).  Loss = mean next-token
NLL of RMSNorm(x)·W_head over the first S-1 positions, plus each layer's
auxiliary loss (a MoE router's) at weight `router_aux_coef / num_layers`,
averaged over the batch rows.  AdamW with global-norm clipping, linear
warm-up and cosine decay, moments stored in the stated dtype.

`rnd` rounds the operands of every product and the embedded rows: the
identity for the reference, `fp8` for the control (the reference one
precision below the bfloat16 the configuration computes in).
"""
from __future__ import annotations

import math
from types import SimpleNamespace
from typing import Callable, Dict, List

import numpy as np

import jax
import jax.numpy as jnp

from yardstick import arch, weights

HI = jax.lax.Precision.HIGHEST
_leaf = jax.jit(weights.leaf, static_argnums=(1, 2, 3))


def identity(x):
    return x


def fp8(x):
    """float8_e4m3fn with a per-tensor scale, straight through in backward."""
    s = jnp.max(jnp.abs(x)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def _divisor(n: int, cap: int) -> int:
    return max(d for d in range(1, min(n, cap) + 1) if n % d == 0)


def lr_at(opt: dict, t: int) -> float:
    warm = min(t / max(opt["warmup_steps"], 1), 1.0)
    frac = min(max((t - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    cos = 0.5 * (1 + math.cos(math.pi * frac))
    return opt["lr"] * warm * (opt["min_lr_ratio"]
                               + (1 - opt["min_lr_ratio"]) * cos)


def _norm(x, s):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * s


def _rope(x, pos, theta: float, fraction: float):
    """Rotate the first `fraction` of each head of x [S, heads, Dh]."""
    rot = int(x.shape[-1] * fraction)
    rot -= rot % 2
    if rot == 0:
        return x
    n = rot // 2
    ang = pos[:, None] * theta ** (-jnp.arange(n, dtype=jnp.float32) / n)
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :n], x[..., n:rot]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s,
                            x[..., rot:]], axis=-1)


def make_ops(rnd: Callable = identity) -> SimpleNamespace:
    """The helpers an architecture module builds its layer from:

      mm(spec, a, b)   einsum of the rounded operands at HIGHEST
      rnd(x)           the rounding of `mm`'s operands
      norm(x, scale)   RMSNorm, eps 1e-6
      rope(x, pos, theta, fraction)
      attention(q [S, H, Dh], k, v [S, K, Dh], window) -> [S, H * Dh]
                       exact causal softmax, a block of queries at a time
    """
    def mm(spec, a, b):
        return jnp.einsum(spec, rnd(a), rnd(b), precision=HI)

    def attention(q, k, v, window: int):
        S, H, Dh = q.shape
        K = k.shape[1]
        k = jnp.repeat(k, H // K, axis=1)
        v = jnp.repeat(v, H // K, axis=1)
        qb = _divisor(S, 512)
        kpos = jnp.arange(S)

        @jax.checkpoint
        def block(args):
            qi, start = args
            sc = mm("qhd,khd->hqk", qi, k) * Dh ** -0.5
            qpos = start + jnp.arange(qb)
            ok = kpos[None, :] <= qpos[:, None]
            if window:
                ok &= (qpos[:, None] - kpos[None, :]) < window
            sc = jnp.where(ok[None], sc, -jnp.inf)
            return mm("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v)

        starts = jnp.arange(0, S, qb)
        out = jax.lax.map(block, (q.reshape(S // qb, qb, H, Dh), starts))
        return out.reshape(S, H * Dh)

    return SimpleNamespace(mm=mm, rnd=rnd, norm=_norm, rope=_rope,
                           attention=attention)


class Reference:
    """Three (or any number of) steps of the plain training step."""

    def __init__(self, cfg: dict, opt: dict, rnd: Callable = identity,
                 device=None):
        self.cfg, self.opt, self.rnd = cfg, opt, rnd
        self.device = device or jax.devices()[0]
        self.arch = arch.module(cfg)
        self.leaves = tuple(self.arch.layer_leaves(cfg))
        ops = make_ops(rnd)
        mm, norm = ops.mm, ops.norm
        layer = self.arch.make_layer(cfg, ops)

        def head_nll(w, s, x, tokens):
            """Sum over positions 0..S-2 of -log p(tokens[t+1])."""
            S = x.shape[0]
            cb = _divisor(S, 512)
            tgt = jnp.roll(tokens, -1)
            keep = (jnp.arange(S) < S - 1).astype(jnp.float32)
            h = norm(x, s)

            @jax.checkpoint
            def block(args):
                hc, tc, kc = args
                lg = mm("sd,dv->sv", hc, w)
                nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
                    lg, tc[:, None], -1)[:, 0]
                return jnp.sum(nll * kc)

            n = S // cb
            return jnp.sum(jax.lax.map(block, (h.reshape(n, cb, -1),
                                               tgt.reshape(n, cb),
                                               keep.reshape(n, cb))))

        def layer_vjp(p, x, dy, daux, l):
            _, pull = jax.vjp(lambda p_, x_: layer(p_, x_, l), p, x)
            return pull((dy, daux))

        def head_vjp(w, s, x, tokens, ct):
            nll, pull = jax.vjp(lambda w_, s_, x_: head_nll(w_, s_, x_, tokens),
                                w, s, x)
            return (nll,) + pull(ct)

        sdt = jnp.dtype(opt["state_dtype"])

        def adam(p, g, m, v, scale, lr, bc1, bc2):
            g = g * scale
            m32 = opt["beta1"] * m.astype(jnp.float32) + (1 - opt["beta1"]) * g
            v32 = opt["beta2"] * v.astype(jnp.float32) \
                + (1 - opt["beta2"]) * g * g
            upd = (m32 / bc1) / (jnp.sqrt(v32 / bc2) + opt["eps"])
            return (p - lr * (upd + opt["weight_decay"] * p),
                    m32.astype(sdt), v32.astype(sdt))

        self._embed = jax.jit(lambda t, tok: rnd(t[tok]))
        self._layer = jax.jit(layer, static_argnums=2)
        self._layer_vjp = jax.jit(layer_vjp, static_argnums=4)
        self._head_vjp = jax.jit(head_vjp)
        self._embed_vjp = jax.jit(
            lambda t, tok, dx: jax.vjp(lambda t_: rnd(t_[tok]), t)[1](dx)[0])
        self._sumsq = jax.jit(lambda g: jnp.sum(g * g))
        self._adam = jax.jit(adam, donate_argnums=(0, 2, 3))

    # ---- state ---------------------------------------------------------
    def init(self, seed: int):
        """Params of `seed` (the benchmark's weights) and zero moments."""
        kd = jax.device_put(weights.key_data(seed), self.device)
        lay = weights.layout(self.cfg)
        p: Dict = {}
        for i, (path, (shape, init)) in enumerate(sorted(lay.items())):
            x = _leaf(
                kd, i, shape, init)
            if path.startswith("layers/"):
                name = path[len("layers/"):]
                for l in range(shape[0]):
                    p[(name, l)] = x[l]
            else:
                p[(path, None)] = x
            del x
        self.p = p
        sdt = jnp.dtype(self.opt["state_dtype"])
        self.m = {k: jnp.zeros(v.shape, sdt) for k, v in p.items()}
        self.v = {k: jnp.zeros(v.shape, sdt) for k, v in p.items()}
        self.t = 0
        self.seed = seed

    def layer_params(self, l: int):
        return {n: self.p[(n, l)] for n in self.leaves}

    # ---- one step --------------------------------------------------------
    def _walk(self, tokens: np.ndarray, visit):
        """Forward and backward over `tokens` [B, S]; hand each finished
        gradient to `visit(key, grad)`.  Returns the loss."""
        B, S = tokens.shape
        L = self.cfg["num_layers"]
        ct = jnp.float32(1.0 / (B * (S - 1)))
        aux_w = self.cfg.get("router_aux_coef", 0.0) / L / B
        daux = jnp.float32(aux_w)
        toks = [jax.device_put(tokens[b], self.device) for b in range(B)]
        xs: List[List] = []
        aux = 0.0
        for b in range(B):
            x = self._embed(self.p[("embed/in_table", None)], toks[b])
            row = [x]
            for l in range(L):
                x, a = self._layer(self.layer_params(l), x, l)
                aux += float(a)
                row.append(x)
            xs.append(row)
        nll = 0.0
        gw = gs = None
        dxs = []
        for b in range(B):
            n, dw, ds, dx = self._head_vjp(self.p[("embed/out_head", None)],
                                           self.p[("final_norm/scale", None)],
                                           xs[b][L], toks[b], ct)
            nll += float(n)
            gw = dw if gw is None else gw + dw
            gs = ds if gs is None else gs + ds
            dxs.append(dx)
        visit(("embed/out_head", None), gw)
        visit(("final_norm/scale", None), gs)
        del gw, gs
        for l in reversed(range(L)):
            pl = self.layer_params(l)
            acc = None
            for b in range(B):
                dp, dxs[b] = self._layer_vjp(pl, xs[b][l], dxs[b], daux, l)
                acc = dp if acc is None else jax.tree.map(jnp.add, acc, dp)
            for name in self.leaves:
                visit((name, l), acc[name])
            del acc
        gt = None
        for b in range(B):
            g = self._embed_vjp(self.p[("embed/in_table", None)], toks[b], dxs[b])
            gt = g if gt is None else gt + g
        visit(("embed/in_table", None), gt)
        return nll / (B * (S - 1)) + aux_w * aux

    def step(self, tokens: np.ndarray) -> dict:
        """One step on `tokens`; returns loss, pre-clip grad norm and the
        clipped gradient's squared norm per program leaf."""
        sq: Dict = {}

        def add_sq(key, g):
            sq[key] = float(self._sumsq(g))

        loss = self._walk(tokens, add_sq)
        gnorm = math.sqrt(sum(sq.values()))
        scale = min(1.0, self.opt["clip_norm"] / max(gnorm, 1e-9)) \
            if self.opt["clip_norm"] else 1.0
        self.t += 1
        t = self.t
        lr = lr_at(self.opt, t)
        bc1 = 1 - self.opt["beta1"] ** t
        bc2 = 1 - self.opt["beta2"] ** t
        args = tuple(jnp.float32(a) for a in (scale, lr, bc1, bc2))

        def update(key, g):
            self.p[key], self.m[key], self.v[key] = self._adam(
                self.p[key], g, self.m[key], self.v[key], *args)

        self._walk(tokens, update)
        return {"loss": loss, "grad_norm": gnorm,
                "grad_sq": _by_leaf(sq, scale * scale)}

    def change_sq(self) -> Dict[str, float]:
        """Squared norm of each program leaf's change since `init`."""
        kd = jax.device_put(weights.key_data(self.seed), self.device)
        lay = weights.layout(self.cfg)
        diff = jax.jit(lambda a, b: jnp.sum((a - b) ** 2))
        out: Dict = {}
        for i, (path, (shape, init)) in enumerate(sorted(lay.items())):
            x0 = _leaf(
                kd, i, shape, init)
            if path.startswith("layers/"):
                name = path[len("layers/"):]
                out[path] = sum(float(diff(self.p[(name, l)], x0[l]))
                                for l in range(shape[0]))
            else:
                out[path] = float(diff(self.p[(path, None)], x0))
            del x0
        return out

    def free(self):
        self.p = self.m = self.v = None


def _by_leaf(sq: Dict, factor: float) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for (name, l), s in sq.items():
        path = name if l is None else "layers/" + name
        out[path] = out.get(path, 0.0) + s * factor
    return out


def readings(cfg: dict, opt: dict, seed: int, batches: List[np.ndarray],
             rnd: Callable = identity, device=None) -> dict:
    """What the checks compare, from the reference (or the control) run
    over `batches`: per-step loss and pre-clip grad norm, the first clipped
    gradient's norm per leaf, and each leaf's change after all steps."""
    ref = Reference(cfg, opt, rnd=rnd, device=device)
    ref.init(seed)
    steps = [ref.step(b) for b in batches]
    change = ref.change_sq()
    ref.free()
    return {"loss": [s["loss"] for s in steps],
            "grad_norm": [s["grad_norm"] for s in steps],
            "grad0": {k: math.sqrt(v) for k, v in steps[0]["grad_sq"].items()},
            "change": {k: math.sqrt(v) for k, v in change.items()}}
