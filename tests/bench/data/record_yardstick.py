"""Record what the yardstick gives, on the CPU, so that a refactor of it can
be held to the same numbers (`test_bench_yardstick.py` reads the file).

    JAX_PLATFORMS=cpu python3 tests/bench/data/record_yardstick.py

For each cell of `BENCHMARK.json`: its tiny configuration and traffic
(`bench_cases.tiny`, `tiny_traffic`), the plain reference's readings over
the first `compare_steps` batches of the ring of seed 2**31 + 3, a sha256
of every leaf of `weights.flat` at that size and seed, and the model FLOPs
per token at the cell's full configuration and sequence.  Writes
`yardstick_parent.json` beside this file (or `--out`).
"""
import argparse
import hashlib
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent)]

from bench_cases import bench_json, cell_files, tiny, tiny_traffic  # noqa: E402

SEED = 2**31 + 3


def leaf_sums(cfg: dict, seed: int) -> dict:
    import jax
    import numpy as np
    from yardstick import weights

    kd = jax.numpy.asarray(weights.key_data(seed))
    flat = jax.jit(lambda k: weights.flat(cfg, k))(kd)
    return {k: hashlib.sha256(np.asarray(v).tobytes()).hexdigest()
            for k, v in sorted(flat.items())}


def record(cell: str) -> dict:
    from yardstick import flops, reference, tokens

    cfg, traffic = tiny(cell), tiny_traffic(cell)
    opt = dict(cfg["optimizer"], total_steps=traffic["steps"])
    ring = tokens.token_ring(SEED, traffic["compare_steps"], traffic["batch"],
                             traffic["seq"], v_eff=traffic["v_eff"],
                             structure=traffic["structure"])
    full_cfg, full_traffic = cell_files(cell)
    return {"tiny_cfg": cfg, "tiny_traffic": traffic, "seed": SEED,
            "readings": reference.readings(cfg, opt, SEED, list(ring)),
            "leaf_sha256": leaf_sums(cfg, SEED),
            "seq": full_traffic["seq"],
            "flops_per_token": flops.train_flops_per_token(
                full_cfg, full_traffic["seq"])}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=str(HERE / "yardstick_parent.json"))
    args = ap.parse_args()
    out = {c["name"]: record(c["name"]) for c in bench_json()["workloads"]}
    Path(args.out).write_text(json.dumps(out, indent=1, sort_keys=True) + "\n")


if __name__ == "__main__":
    main()
