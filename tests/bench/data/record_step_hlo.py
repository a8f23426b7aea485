"""Record the compiled HLO text of a tiny train step, on the CPU, for the
tests of `yardstick.trace.scope_map` and `scope_of`.

    JAX_PLATFORMS=cpu python3 tests/bench/data/record_step_hlo.py

Builds the chatglm3 train cell at its tiny size (`bench_cases.tiny`),
runs its first step through `Trainer.run` and writes the executable's
`as_text()` to `step_hlo_tiny.txt.gz` beside this file (or `--out`).
"""
import argparse
import gzip
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent)]

from bench_cases import tiny, tiny_traffic  # noqa: E402

CELL = "chatglm3-6b.train-s2k.1chip"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=str(HERE / "step_hlo_tiny.txt.gz"))
    args = ap.parse_args()
    import jax
    from drivers import train

    b = train.build(tiny(CELL), tiny_traffic(CELL), 3, jax.devices(),
                    warmup=1, seconds=0.0)
    train.drive(b, 3)
    Path(args.out).write_bytes(
        gzip.compress(b.trainer.compiled.as_text().encode(), mtime=0))


if __name__ == "__main__":
    main()
