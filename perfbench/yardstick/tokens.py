"""Training inputs made from the seed: the Markov token stream, vectorised.

The stream is the one `repro.data.SyntheticTokens` draws (token t+1 is
`(31 * x_t + 7) % v_eff` with probability `structure`, else uniform in
`[0, v_eff)`), computed without a Python loop over positions: a position
either restarts the chain with a random token or continues the chain from
the last restart, and `k` steps of the affine map from `x` are
`(a_k * x + b_k) % v_eff`.  The draws differ from `SyntheticTokens`'
(another generator order), the law does not.
"""
from __future__ import annotations

import numpy as np

MUL, ADD = 31, 7


def _affine_powers(n: int, v_eff: int):
    """a_k = 31^k and b_k = 7 * (31^(k-1) + ... + 1), both mod v_eff."""
    a = np.ones(n, np.int64)
    b = np.zeros(n, np.int64)
    for k in range(1, n):
        a[k] = a[k - 1] * MUL % v_eff
        b[k] = (b[k - 1] * MUL + ADD) % v_eff
    return a, b


def markov_tokens(seed: int, index: int, batch: int, seq: int, *,
                  v_eff: int = 4096, structure: float = 0.8) -> np.ndarray:
    """[batch, seq] int32 tokens of batch `index` of the stream of `seed`."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), int(index)]))
    restart = rng.random((batch, seq)) >= structure
    restart[:, 0] = True
    fresh = rng.integers(0, v_eff, (batch, seq))
    pos = np.arange(seq)
    last = np.maximum.accumulate(np.where(restart, pos, 0), axis=1)
    a, b = _affine_powers(seq, v_eff)
    k = pos[None, :] - last
    x0 = np.take_along_axis(fresh, last, axis=1)
    return ((a[k] * x0 + b[k]) % v_eff).astype(np.int32)


def token_ring(seed: int, size: int, batch: int, seq: int, **kw) -> np.ndarray:
    """[size, batch, seq]: the distinct batches a train cell cycles through."""
    return np.stack([markov_tokens(seed, i, batch, seq, **kw)
                     for i in range(size)])
