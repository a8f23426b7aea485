"""Readings that set the limits of a train cell's checks (run on the chip).

    python3 perfbench/control.py --workload <name> --seeds 1,2,...,12 \\
        --control-seeds 1,2,3 [--fault-seeds 4,5,6] [--out readings.json]

For each seed of `--seeds`, the program's first steps through
`Trainer.run` (as a benchmark run drives them, with a window of one
step) against the plain reference: the lower readings.  For each seed of
`--control-seeds`, the control (the reference one precision below the
configuration's bfloat16: fp8 operands, `reference.fp8`) in the
program's place: the upper readings.  Each row is also judged against
the cell's limits (`limits/<workload>.json`), as a benchmark run would
be: the program's rows should come out correct, the control's and the
half-batch fault's not.  Everything runs in this one process, which
holds the chip; the benchmark's own runs never run this.
"""
import argparse
import gc
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

import run as harness  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="",
                    help="seeds of the half-batch fault, planted in the "
                         "reference put in the program's place")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    bench, cell, cfg, traffic = harness.find_cell(args.workload)
    devices = harness.require_devices(cell["chips"])
    harness.use_compile_cache()
    from drivers import train
    from yardstick import compare, reference, tokens

    opt = dict(cfg["optimizer"], total_steps=traffic["steps"])
    n = traffic["compare_steps"]
    limits = compare.load_limits(HERE, cell["name"])
    rows = []

    def add(side, seed, prog, ref, **extra):
        gaps = compare.train_gaps(prog, ref)
        rows.append(dict(side=side, seed=seed, gaps=gaps,
                         correct=compare.judge(gaps, limits)[0],
                         prog=prog, ref=ref, **extra))
        print(json.dumps({k: v for k, v in rows[-1].items()
                          if k not in ("prog", "ref")}), flush=True)

    for seed in [int(s) for s in args.seeds.split(",") if s]:
        t0 = time.perf_counter()
        b = train.build(cfg, traffic, seed, devices,
                        warmup=traffic["warmup_steps"], seconds=0.0)
        train.drive(b, seed)
        prog = train.program_readings(b, n)
        ring = b.ring_np[:n]
        del b
        gc.collect()
        t1 = time.perf_counter()
        ref = reference.readings(cfg, opt, seed, list(ring), device=devices[0])
        t2 = time.perf_counter()
        add("program", seed, prog, ref, program_s=t1 - t0,
            reference_s=t2 - t1)
    for seed in [int(s) for s in args.control_seeds.split(",") if s]:
        ring = tokens.token_ring(seed, n, traffic["batch"], traffic["seq"],
                                 v_eff=traffic["v_eff"],
                                 structure=traffic["structure"])
        ref = reference.readings(cfg, opt, seed, list(ring), device=devices[0])
        ctl = reference.readings(cfg, opt, seed, list(ring),
                                 rnd=reference.fp8, device=devices[0])
        add("control", seed, ctl, ref)
    for seed in [int(s) for s in args.fault_seeds.split(",") if s]:
        ring = tokens.token_ring(seed, n, traffic["batch"], traffic["seq"],
                                 v_eff=traffic["v_eff"],
                                 structure=traffic["structure"])
        half = [r[: len(r) // 2] if len(r) > 1 else r[:, : r.shape[1] // 2]
                for r in ring]
        ref = reference.readings(cfg, opt, seed, list(ring), device=devices[0])
        bad = reference.readings(cfg, opt, seed, half, device=devices[0])
        add("half_batch", seed, bad, ref)
    summary = {}
    for side in ("program", "control", "half_batch"):
        g = [r["gaps"] for r in rows if r["side"] == side]
        if g:
            summary[side] = {k: {"max": max(x[k] for x in g),
                                 "min": min(x[k] for x in g)} for k in g[0]}
            summary[side]["correct"] = sum(r["correct"] for r in rows
                                           if r["side"] == side)
            summary[side]["runs"] = len(g)
    print(json.dumps({"workload": args.workload, "summary": summary}))
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(
            {"workload": args.workload, "rows": rows, "summary": summary},
            indent=1))


if __name__ == "__main__":
    main()
