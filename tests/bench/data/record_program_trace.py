"""Record the small trace with the training loop's spans that
`test_bench_program.py` reduces (run on the chip).

    python3 tests/bench/data/record_program_trace.py

Drives the one-chip chatglm3 train cell for a short traced window, keeps
the device ops, the benchmark's spans and the program's `train.*` spans of
the first three whole `train.step`s of the window, and writes them, with
what `yardstick.program` gives on them, to `trace_record_program.json.gz`
beside this file (or under `--out`).
"""
import argparse
import gzip
import json
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[2]
sys.path[:0] = [str(ROOT / "perfbench"), str(ROOT / "src")]

import run as harness  # noqa: E402

STEPS = 3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="chatglm3-6b.train-s2k.1chip")
    ap.add_argument("--out", default=str(HERE / "trace_record_program.json.gz"))
    args = ap.parse_args()
    bench, cell, cfg, traffic = harness.find_cell(args.workload)
    devices = harness.require_devices(cell["chips"])
    harness.use_compile_cache()
    import jax
    from drivers import train
    from yardstick import program, trace

    tdir = tempfile.mkdtemp()
    spans = {}

    def on_open():
        jax.profiler.start_trace(tdir)
        spans["w"] = jax.profiler.TraceAnnotation("bench.window")
        spans["w"].__enter__()

    b = train.build(cfg, traffic, 7, devices, warmup=traffic["warmup_steps"],
                    seconds=1.2, on_open=on_open,
                    on_close=lambda: spans["w"].__exit__(None, None, None))
    try:
        train.drive(b, 7)
    finally:
        jax.profiler.stop_trace()
    rec = trace.load(tdir)
    rec.update(program.load(tdir))
    shutil.rmtree(tdir, ignore_errors=True)
    lo, hi = trace.window(rec)
    # the step that opened the window began before the trace did: start
    # at the first whole step
    starts = [s for n, s, _d, _k in rec["program"]
              if n == program.STEP_SPAN and lo <= s < hi]
    lo, end = starts[0], starts[STEPS]
    keep = lambda s, d: s < end and s + d > lo  # noqa: E731
    rec["spans"] = [[n, s, d] for n, s, d in rec["spans"]
                    if keep(s, d) and n != "bench.window"]
    rec["spans"].append(["bench.window", lo, end - lo])
    rec["program"] = [p for p in rec["program"] if keep(p[1], p[2])]
    rec["devices"] = {p: [[n, s, d] for n, s, d in ops if keep(s, d)]
                      for p, ops in rec["devices"].items()}
    rec["expect"] = {
        "steps": STEPS,
        "busy_ns": trace.busy_ns(rec, lo, end),
        "phase_idle": program.phase_idle(rec, lo, end),
        "loop_gap_ms": program.loop_gap_ms(rec, lo, end, STEPS)}
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_bytes(gzip.compress(json.dumps(rec).encode()))
    print(json.dumps(rec["expect"]), len(json.dumps(rec)))


if __name__ == "__main__":
    main()
