"""Architecture modules, one file each, found by name.

A configuration's `arch` key (its `family` where it has none) names
`arch/<arch>.py`.  The module says what is particular to the
architecture, and the rest of the yardstick (the leaf init, the key
data, the walk over layers, the head and loss, clipping and AdamW, the
FLOPs of attention) is shared:

  layout(cfg)        {leaf path: (shape, init)}, the program's param tree
                     leaf for leaf; per-layer leaves sit under `layers/`
                     with the layer on a leading axis
  layer_leaves(cfg)  the names under `layers/` in the order the walk
                     visits them
  make_layer(cfg, ops)
                     `layer(p, x, l) -> (x, aux)` for one batch row
                     x [S, D] at layer index `l` (static), with `p` the
                     layer's leaves by name and `aux` the layer's
                     auxiliary loss (0 where there is none); `ops` are the
                     reference's helpers, see `reference.make_ops`
  matmul_flops_per_token(cfg)
                     forward FLOPs of every matrix product a token makes,
                     the LM head included
  attention_keys(cfg, seq)
                     per layer, the mean number of keys a query attends to
  tiny(cfg)          the configuration at widths a CPU test holds
"""
from __future__ import annotations

import importlib
from types import ModuleType


def name(cfg: dict) -> str:
    return cfg.get("arch", cfg["family"])


def module(cfg: dict) -> ModuleType:
    """The architecture module of a configuration."""
    full = f"{__name__}.{name(cfg)}"
    try:
        return importlib.import_module(full)
    except ModuleNotFoundError as e:
        if e.name != full:
            raise
        raise ValueError(f"no architecture module arch/{name(cfg)}.py for "
                         f"config {cfg['name']!r}") from None
