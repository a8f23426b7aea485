"""The yardstick on the CPU: trace reduction, inputs, window arithmetic,
FLOP count, weights and the reference."""
import gzip
import json
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench_cases import BENCH, DATA, bench_json, tiny
from drivers import train
from yardstick import compare, flops, reference, tokens, trace, weights


# ---- trace reduction ---------------------------------------------------

def _record():
    path = DATA / "trace_record.json.gz"
    return json.loads(gzip.decompress(path.read_bytes()))


def test_trace_reduction_on_recorded_trace():
    rec = _record()
    lo, hi = trace.window(rec)
    busy = trace.busy_ns(rec, lo, hi)
    want = rec["expect"]
    assert {p: v for p, v in busy.items()} == want["busy_ns"]
    ops = trace.top_ops(rec, lo, hi, n=3)
    assert [n for n, _ in ops] == want["top_ops"]
    gaps = trace.idle_gaps(rec, lo, hi, n=3)
    assert [n for n, _ in gaps] == want["gap_spans"]
    assert gaps[0][1] == pytest.approx(want["longest_gap_s"])
    # one chip, three steps: busy most of the window, the ops' own times
    # add up to the busy time, no gap in batch making
    (chip_busy,) = busy.values()
    assert 0.9 * (hi - lo) < chip_busy <= hi - lo
    own = sum(o for _n, _iv, o, _k in trace.self_times(
        trace._ops(rec, trace.sorted_planes(rec)[0], lo, hi)))
    # the trace rounds each op's ps to ns: a few ns of overlap per op
    assert own == pytest.approx(chip_busy, rel=1e-4)
    assert "bench.batch_at" not in want["gap_spans"]


def test_interval_arithmetic():
    assert trace.measure([(0, 5), (3, 8), (10, 12)]) == 10
    assert trace.clip([(0, 10), (15, 30)], 5, 20) == [(5, 10), (15, 20)]


# ---- inputs --------------------------------------------------------------

def test_ring_is_deterministic_in_the_seed():
    big = 2**31 + 12345
    a = tokens.token_ring(big, 4, 2, 256)
    b = tokens.token_ring(big, 4, 2, 256)
    c = tokens.token_ring(big + 1, 4, 2, 256)
    assert a.dtype == np.int32 and a.shape == (4, 2, 256)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert len({r.tobytes() for r in a}) == 4          # distinct batches


def test_tokens_follow_the_markov_law():
    t = tokens.markov_tokens(7, 0, 4, 4096, v_eff=4096, structure=0.8)
    follows = (t[:, 1:] == (t[:, :-1] * 31 + 7) % 4096).mean()
    assert 0.78 < follows < 0.82
    assert t.min() >= 0 and t.max() < 4096


def test_loop_and_vectorised_streams_agree_on_the_chain():
    """Between restarts the vectorised stream is the affine chain."""
    t = tokens.markov_tokens(3, 1, 1, 512, structure=1.0)[0]
    x = [int(t[0])]
    for _ in range(511):
        x.append((x[-1] * 31 + 7) % 4096)
    assert t.tolist() == x


def test_ring_entries_pass_through_without_a_copy():
    dev = jax.devices()[0]
    ring = [{"tokens": jax.device_put(np.arange(8, dtype=np.int32).reshape(1, 8)
                                      + i, dev)} for i in range(3)]
    data = train.RingData(ring, warmup=100, seconds=1.0)
    got = data.batch_at(4)["tokens"]
    assert got is ring[1]["tokens"]
    assert jnp.asarray(got) is got                 # Trainer.run's conversion
    assert got.sharding == ring[1]["tokens"].sharding


def test_window_closes_by_raising():
    ring = [{"tokens": jnp.zeros((1, 4), jnp.int32)}]
    data = train.RingData(ring, warmup=2, seconds=0.0)
    for k in range(3):
        data.batch_at(k)
    with pytest.raises(train.WindowClosed):
        data.batch_at(3)
    assert len(data.stamps) == 1 and data.t_close >= data.t_open


# ---- window arithmetic ---------------------------------------------------

def test_window_metrics_on_given_stamps():
    stamps = [10.0, 10.1, 10.3, 10.4, 10.6]
    w = train.window_metrics(stamps, 10.8, tokens_per_step=2048)
    assert w["steps"] == 5
    assert w["tokens_per_s"] == pytest.approx(5 * 2048 / 0.8)
    # intervals 0.1, 0.2, 0.1, 0.2, 0.2 -> p90 by linear interpolation
    assert w["step_ms_p90"] == pytest.approx(200.0)
    w = train.window_metrics([0.0, 1.0], 3.0, 1)
    assert w["step_ms_p90"] == pytest.approx(1900.0)


# ---- FLOPs -----------------------------------------------------------------

def test_flops_chatglm_by_hand():
    cfg = json.loads((BENCH / "configs" / "chatglm3-6b-l2.json").read_text())
    D, F, V = 4096, 13696, 65024
    per_layer = D * 4096 + 2 * D * 256 + 4096 * D + 3 * D * F
    assert per_layer == 203_948_032
    n = 2 * per_layer + D * V
    assert flops.matmul_weights(cfg) == n == 674_234_368
    attn = 2 * 2 * 32 * 128 * (2049 / 2)
    want = 3 * (2 * n + 2 * attn)
    assert flops.train_flops_per_token(cfg, 2048) == pytest.approx(want)
    assert flops.train_flops_per_token(cfg, 2048) == pytest.approx(4.1459e9,
                                                                  rel=1e-4)


def test_window_caps_the_keys():
    assert flops.mean_keys(4, 0) == 2.5
    assert flops.mean_keys(6, 2) == (1 + 2 + 2 * 4) / 6
    assert flops.mean_keys(4608, 4096) == pytest.approx(
        (4096 * 4097 / 2 + 512 * 4096) / 4608)


def test_unknown_device_kind_is_an_error():
    assert flops.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(ValueError):
        flops.peak_flops("cpu")


# ---- weights and the reference -------------------------------------------

def test_weights_are_the_models_tree_and_seeded():
    from repro.models import api
    cfg = tiny()
    mcfg = train.model_config(cfg)
    got = jax.tree.map(lambda s: s.shape, jax.eval_shape(
        lambda k: weights.nest(weights.flat(cfg, k)),
        jnp.zeros(2, jnp.uint32)))
    want = jax.tree.map(lambda s: s.shape, api.abstract_params(mcfg))
    assert got == want
    a = weights.make_params(cfg, 2**32 + 9)
    b = weights.make_params(cfg, 2**32 + 9)
    c = weights.make_params(cfg, 9)
    leaf = lambda t: np.asarray(t["layers"]["attn"]["wq"])
    assert np.array_equal(leaf(a), leaf(b))
    assert not np.array_equal(leaf(a), leaf(c))


def test_reference_matches_program_loss_in_f32():
    """At f32 throughout, the program's loss and the reference's agree."""
    from repro.models import api
    cfg = tiny(compute_dtype="float32")
    mcfg = train.model_config(cfg)
    seed = 5
    params = weights.make_params(cfg, seed)
    toks = tokens.markov_tokens(seed, 0, 2, 64, v_eff=256)
    with jax.default_matmul_precision("highest"):
        want = float(api.loss_fn(mcfg, params, {"tokens": jnp.asarray(toks)}))
    opt = dict(cfg["optimizer"], total_steps=100)
    ref = reference.Reference(cfg, opt)
    ref.init(seed)
    got = ref.step(toks)["loss"]
    assert got == pytest.approx(want, rel=1e-5)


def test_reference_adam_by_hand():
    opt = {"lr": 1e-3, "warmup_steps": 20, "total_steps": 100,
           "min_lr_ratio": 0.1}
    assert reference.lr_at(opt, 1) == pytest.approx(1e-3 / 20)
    assert reference.lr_at(opt, 100) == pytest.approx(1e-4)


def test_leaf_gap_uses_the_median_floor():
    want = {"a": 1.0, "b": 2.0, "c": 1e-6}
    got = {"a": 1.1, "b": 2.0, "c": 2e-6}
    # c's own norm is tiny: its gap is measured against the median (1.0)
    assert compare.leaf_gap(got, want) == pytest.approx(0.1)
    assert compare.roundoff_leaves(want) == {"c"}


# ---- the harness ---------------------------------------------------------

def _harness():
    import importlib
    return importlib.import_module("run")


@pytest.mark.parametrize("cell", [c["name"] for c in bench_json()["workloads"]])
def test_each_cell_reports_its_metrics(cell):
    """Every cell reports `setup_s`, another end-to-end metric and a
    per-layer one, and its files are found by name."""
    h = _harness()
    bench, c, cfg, traffic = h.find_cell(cell)
    e2e = {m["name"] for m in h.metrics_of(bench, c, trace=False)}
    per_layer = {m["name"] for m in h.metrics_of(bench, c, trace=True)}
    assert "setup_s" in e2e and len(e2e) >= 2 and per_layer
    assert all(h.reader(n) for n in per_layer)
    assert compare.load_limits(BENCH, cell)
    assert cfg["name"] == c["config"] and traffic["kind"] == "train"


def test_readers_on_recorded_trace():
    from types import SimpleNamespace
    h = _harness()
    rec = _record()
    lo, hi = trace.window(rec)
    busy = trace.busy_ns(rec, lo, hi)
    t = SimpleNamespace(rec=rec, lo=lo, hi=hi, busy=busy, n_steps=3,
                        tokens_per_s=12000.0, chips=1,
                        device_kind="TPU v5 lite", flops_per_token=4.0e9)
    idle = h.reader("device_idle_pct")(t)
    (chip_busy,) = busy.values()
    assert idle == pytest.approx(100 * (1 - chip_busy / (hi - lo)))
    assert 0 < idle < 10
    assert h.reader("mfu")(t) == pytest.approx(100 * 4.0e9 * 12000 / 197e12)


def test_no_chip_no_result(tmp_path):
    """In a directory with only the benchmark's files and no TPU, a run
    exits non-zero and prints no result."""
    import os
    import shutil
    import subprocess
    import sys
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    res = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "chatglm3-6b.train-s2k.1chip", "--seed", str(2**31 + 5),
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
