"""The training loop's spans in a trace: device idle split by loop phase,
on hand-built records, on a recorded chip trace, and read back from a
profiler trace on the CPU."""
import gzip
import json

import pytest

import jax

from bench_cases import DATA
from yardstick import program, trace

PLANE = "/device:TPU:0"


def _rec(ops, spans):
    """A record with one chip's ops [(start, end)] and program spans
    [(name, start, end)]."""
    return {"devices": {PLANE: [[f"op.{i}", s, e - s]
                                for i, (s, e) in enumerate(ops)]},
            "spans": [],
            "program": [[n, s, e - s, None] for n, s, e in spans]}


def _step(t0):
    """One loop step from t0: data 0-10, dispatch 10-30, wait 30-130,
    fetch 130-140, log 140-150."""
    return [("train.step", t0, t0 + 150), ("train.data", t0, t0 + 10),
            ("train.dispatch", t0 + 10, t0 + 30),
            ("train.wait", t0 + 30, t0 + 130),
            ("train.fetch", t0 + 130, t0 + 140),
            ("train.log", t0 + 140, t0 + 150)]


def test_gap_is_split_across_adjacent_phases():
    # the device runs 25-120 and 180-280: the gap 120-180 lies in wait
    # (120-130), fetch, log, then the next step's data and dispatch
    rec = _rec([(25, 120), (180, 280)], _step(0) + _step(150))
    by = program.phase_idle(rec, 0, 300)[PLANE]
    assert by == {"train.data": 10 + 10, "train.dispatch": 15 + 20,
                  "train.wait": 10, "train.fetch": 10 + 10,
                  "train.log": 10 + 10}
    # the idle inside `train.wait` is not the loop's
    assert program.loop_gap_ms(rec, 0, 300, 2) == pytest.approx(
        (20 + 35 + 20 + 20) / 2 * 1e-6)


def test_untraced_remainder_and_innermost_span():
    # a compile inside the first dispatch; nothing open from 150 to 170
    spans = _step(0) + [("train.compile", 12, 28)] + \
        [(n, s + 20, e + 20) for n, s, e in _step(150)]
    rec = _rec([(40, 100)], spans)
    by = program.phase_idle(rec, 0, 200)[PLANE]
    assert by["train.compile"] == 16
    assert by["train.dispatch"] == 2 + 2 + 20
    assert by[program.UNTRACED] == 20
    assert sum(by.values()) == 200 - 60
    # a span the phases leave open is charged to the step itself
    rec = _rec([], [("train.step", 0, 100), ("train.data", 0, 40)])
    assert program.phase_idle(rec, 0, 100)[PLANE] == {
        "train.data": 40, "train.step": 60}


def test_no_program_spans_reads_nothing():
    """A trace of a loop without the spans gives no reading, and no error."""
    rec = {"devices": {PLANE: [["op", 0, 10]]}, "spans": []}
    assert program.phase_idle(rec, 0, 20) is None
    assert program.loop_gap_ms(rec, 0, 20, 1) is None
    rec["program"] = []
    assert program.loop_gap_ms(rec, 0, 20, 1) is None


def test_program_reduction_on_recorded_trace():
    rec = json.loads(gzip.decompress(
        (DATA / "trace_record_program.json.gz").read_bytes()))
    want = rec["expect"]
    lo, hi = trace.window(rec)
    n = want["steps"]
    assert trace.busy_ns(rec, lo, hi) == want["busy_ns"]
    idle = program.phase_idle(rec, lo, hi)
    assert idle == want["phase_idle"]
    gap = program.loop_gap_ms(rec, lo, hi, n)
    assert gap == pytest.approx(want["loop_gap_ms"])
    steps = [p for p in rec["program"] if p[0] == program.STEP_SPAN]
    assert [p[3] for p in steps] == list(range(steps[0][3], steps[0][3] + n))
    (by,) = idle.values()
    (busy,) = want["busy_ns"].values()
    # the phases tile the steps, and the loop's gap is part of the idle
    assert sum(by.values()) == hi - lo - busy
    assert by.get(program.UNTRACED, 0) < 0.05 * sum(by.values())
    assert 0 < gap * n * 1e6 <= sum(by.values())


def test_load_reads_the_program_spans(tmp_path):
    """`load` keeps the `train.*` host spans with each step's number, and
    leaves the benchmark's own spans to `trace.load`."""
    with jax.profiler.trace(str(tmp_path)):
        for k in (7, 8):
            with jax.profiler.StepTraceAnnotation("train.step", step_num=k):
                with jax.profiler.TraceAnnotation("train.data"):
                    with jax.profiler.TraceAnnotation("bench.batch_at"):
                        pass
                with jax.profiler.TraceAnnotation("train.wait"):
                    jax.block_until_ready(jax.numpy.ones(4) * 2)
    got = program.load(str(tmp_path))["program"]
    assert [(n, k) for n, _s, _d, k in got] == [
        ("train.step", 7), ("train.data", None), ("train.wait", None),
        ("train.step", 8), ("train.data", None), ("train.wait", None)]
    step, data = got[0], got[1]
    assert step[1] <= data[1] and data[1] + data[2] <= step[1] + step[2]
