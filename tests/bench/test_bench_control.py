"""The control comes out not correct: the plain reference computed one
precision below the configuration's bfloat16 (fp8 operands with a
per-tensor scale, `reference.fp8`), put in the program's place and held
to each cell's limits.  On the chip `perfbench/control.py` reads it at
the cell's own size; here it runs at a size a test run can hold."""
import pytest

from bench_cases import BENCH, bench_json, tiny, tiny_traffic
from yardstick import compare, reference, tokens

CELLS = [c["name"] for c in bench_json()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [1, 2**31 + 11])
def test_control_fails_the_limits(cell, seed):
    cfg, traffic = tiny(cell), tiny_traffic(cell)
    opt = dict(cfg["optimizer"], total_steps=traffic["steps"])
    ring = tokens.token_ring(seed, traffic["compare_steps"], traffic["batch"],
                             traffic["seq"], v_eff=traffic["v_eff"],
                             structure=traffic["structure"])
    ref = reference.readings(cfg, opt, seed, list(ring))
    ctl = reference.readings(cfg, opt, seed, list(ring), rnd=reference.fp8)
    limits = compare.load_limits(BENCH, cell)
    assert limits, f"no limits for {cell}"
    ok, checks = compare.judge(compare.train_gaps(ctl, ref), limits)
    assert not ok, checks
