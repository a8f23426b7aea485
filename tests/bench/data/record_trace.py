"""Record the small trace `test_bench_yardstick.py` reduces (run on the chip).

    python3 tests/bench/data/record_trace.py

Drives the one-chip chatglm3 train cell for a short traced window, keeps
the device ops and benchmark spans of the first four window steps, and
the executable's op-name path of each op among them (`trace.scope_map`),
and writes them, with what the reductions give on them, to
`trace_record.json.gz` beside this file (or under `--out`).
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

STEPS = 4


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="chatglm3-6b.train-s2k.1chip")
    ap.add_argument("--out", default=str(HERE / "trace_record.json.gz"))
    args = ap.parse_args()
    bench, cell, cfg, traffic = harness.find_cell(args.workload)
    devices = harness.require_devices(cell["chips"])
    harness.use_compile_cache()
    import jax
    from drivers import train
    from yardstick import trace

    tdir = tempfile.mkdtemp()
    spans = {}

    def on_open():
        jax.profiler.start_trace(tdir)
        spans["w"] = jax.profiler.TraceAnnotation("bench.window")
        spans["w"].__enter__()

    b = train.build(cfg, traffic, 7, devices, warmup=traffic["warmup_steps"],
                    seconds=0.6, on_open=on_open,
                    on_close=lambda: spans["w"].__exit__(None, None, None))
    try:
        train.drive(b, 7)
    finally:
        jax.profiler.stop_trace()
    scopes = trace.scope_map(b.trainer.compiled.as_text())
    rec = trace.load(tdir)
    shutil.rmtree(tdir, ignore_errors=True)
    lo, hi = trace.window(rec)
    # the data call that opened the window began before it: the next
    # calls' stamps start window steps 1, 2, ...
    stamps = [int(s) for n, s, d in sorted(rec["spans"], key=lambda x: x[1])
              if n == "bench.batch_at" and lo <= s < hi]
    end = stamps[STEPS - 1]
    rec["spans"] = [[n, s, d] for n, s, d in rec["spans"]
                    if s < end and s + d > lo and n != "bench.window"]
    rec["spans"].append(["bench.window", lo, end - lo])
    rec["devices"] = {p: [[n, s, d] for n, s, d in ops if s < end and s + d > lo]
                      for p, ops in rec["devices"].items()}
    names = {n for ops in rec["devices"].values() for n, _s, _d in ops}
    rec["scopes"] = {k: v for k, v in scopes.items() if k in names}
    gaps = trace.idle_gaps(rec, lo, end, n=3)
    rec["expect"] = {
        "busy_ns": trace.busy_ns(rec, lo, end),
        "top_ops": [n for n, _ in trace.top_ops(rec, lo, end, n=3)],
        "gap_spans": [n for n, _ in gaps],
        "longest_gap_s": gaps[0][1],
        "steps": STEPS,
        "scope_ms": {s: trace.scope_ms(rec, lo, end, STEPS, [s])
                     for s in trace.SCOPES}}
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_bytes(gzip.compress(json.dumps(rec).encode()))
    print(json.dumps(rec["expect"]), len(json.dumps(rec)))


if __name__ == "__main__":
    main()
