"""The yardstick on the CPU: trace reduction, inputs, window arithmetic,
FLOP count, weights and the reference."""
import gzip
import hashlib
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench_cases import BENCH, DATA, bench_json, cell_files, tiny, tiny_traffic
from drivers import train
from yardstick import arch, compare, flops, reference, tokens, trace, weights
from yardstick.arch import dense


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


HLO = """\
ENTRY %main {
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(step)/transpose(jvp(layer))/attn/dot_general" stack_frame_id=3}
  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(step)/jvp(loss)/while/body/closed_call/checkpoint/logits/dot_general"}
  %add.3 = f32[8]{0} add(%p, %p), metadata={op_name="jit(step)/jvp(loss)/div"}
  %fusion.4 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(step)/layer/mlp/mul"}
  %fusion.5 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(step)/jvp()/while/body/transpose(jvp(layer))/mul"}
  %copy.6 = f32[8]{0} copy(%p)
  %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(step)/attn_decode/layers/mul"}
  ROOT %while.8 = f32[8]{0} while(%p), condition=%c, body=%b, metadata={op_name="jit(step)/optimizer/while"}
  %tokens.9 = s32[8]{0} parameter(0), metadata={op_name="batch[\\'tokens\\']"}
}
"""


def test_scope_rule():
    """Transform wrappers stripped, whole components only, the innermost
    scope first; an op with no metadata or no scope counts in no metric."""
    scopes = trace.scope_map(HLO)
    assert scopes["fusion.1"] == "jit(step)/transpose(jvp(layer))/attn/dot_general"
    assert scopes["tokens.9"] == "batch[\\'tokens\\']"
    assert "copy.6" not in scopes
    got = {k: trace.scope_of(v) for k, v in scopes.items()}
    assert got == {"fusion.1": "attn", "fusion.2": "logits", "add.3": "loss",
                   "fusion.4": "mlp", "fusion.5": "layer", "fusion.7": None,
                   "while.8": "optimizer", "tokens.9": None}
    # two chips, two steps; the loop's body op fusion.4 lies inside
    # while.8, which keeps only its own 30 ns
    ops = [["fusion.1", 0, 100], ["fusion.2", 100, 50], ["add.3", 150, 10],
           ["copy.6", 160, 40], ["fusion.7", 200, 20],
           ["while.8", 220, 80], ["fusion.4", 230, 50]]
    rec = {"devices": {"/device:TPU:0": ops,
                       "/device:TPU:1": [[n, 3 * s, 3 * d] for n, s, d in ops]},
           "spans": [], "scopes": scopes}

    def ms(*names):
        return trace.scope_ms(rec, 0, 10_000, 2, names)
    assert ms("attn") == pytest.approx((100 + 300) / 2 / 2 * 1e-6)
    assert ms("embed", "logits", "loss") == pytest.approx(
        (60 + 180) / 2 / 2 * 1e-6)
    assert ms("optimizer") == pytest.approx((30 + 90) / 2 / 2 * 1e-6)
    assert ms("mlp") == pytest.approx((50 + 150) / 2 / 2 * 1e-6)
    assert ms("embed") is None              # no op of the scope ran
    assert trace.scope_ms(dict(rec, scopes=None), 0, 10_000, 2,
                          ["attn"]) is None


def test_scope_map_of_a_compiled_step():
    """On a tiny train step's compiled HLO (recorded on the CPU): every
    scope of the program is found, backward ops under their wrappers."""
    text = gzip.decompress((DATA / "step_hlo_tiny.txt.gz").read_bytes()).decode()
    scopes = trace.scope_map(text)
    by = {}
    for name, path in scopes.items():
        by.setdefault(trace.scope_of(path), []).append(path)
    assert set(by) == set(trace.SCOPES) | {None}
    for s, paths in by.items():
        assert s is None or all(s in p for p in paths)
        if s not in (None, "optimizer"):
            # the model's backward: the scope inside `transpose(jvp(...))`
            # or below it
            assert any("transpose(" in p for p in paths), s
    # the scan loops themselves, outside every scope
    assert any(p.endswith("jvp()/while") for p in by[None])
    # instruction names are the trace's op names: `%` and `ROOT` dropped
    assert all(not n.startswith(("%", "ROOT")) for n in scopes)
    assert len(scopes) > 1000


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
    assert dense.matmul_weights(cfg) == n == 674_234_368
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


PARENT = json.loads((DATA / "yardstick_parent.json").read_text())


@pytest.mark.parametrize("cell", sorted(PARENT))
def test_yardstick_reproduces_its_record(cell):
    """The reference's readings, the weights and the FLOP count are, to
    the last bit, what the yardstick gave before its architecture parts
    were split out (`data/record_yardstick.py`, CPU)."""
    want = PARENT[cell]
    cfg, traffic = tiny(cell), tiny_traffic(cell)
    assert cfg == want["tiny_cfg"] and traffic == want["tiny_traffic"]
    seed = want["seed"]
    opt = dict(cfg["optimizer"], total_steps=traffic["steps"])
    ring = tokens.token_ring(seed, traffic["compare_steps"], traffic["batch"],
                             traffic["seq"], v_eff=traffic["v_eff"],
                             structure=traffic["structure"])
    assert reference.readings(cfg, opt, seed, list(ring)) == want["readings"]
    flat = jax.jit(lambda k: weights.flat(cfg, k))(
        jnp.asarray(weights.key_data(seed)))
    assert {k: hashlib.sha256(np.asarray(v).tobytes()).hexdigest()
            for k, v in flat.items()} == want["leaf_sha256"]
    full, _ = cell_files(cell)
    assert flops.train_flops_per_token(full, want["seq"]) \
        == want["flops_per_token"]


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
    assert cfg["name"] == c["config"]
    assert (BENCH / "drivers" / f"{traffic['kind']}.py").exists()
    assert (BENCH / "yardstick" / "arch" / f"{arch.name(cfg)}.py").exists()


def test_readers_on_recorded_trace():
    from types import SimpleNamespace
    h = _harness()
    rec = _record()
    lo, hi = trace.window(rec)
    busy = trace.busy_ns(rec, lo, hi)
    t = SimpleNamespace(rec=rec, lo=lo, hi=hi, busy=busy,
                        n_steps=rec["expect"]["steps"],
                        tokens_per_s=12000.0, chips=1,
                        device_kind="TPU v5 lite", flops_per_token=4.0e9)
    idle = h.reader("device_idle_pct")(t)
    (chip_busy,) = busy.values()
    assert idle == pytest.approx(100 * (1 - chip_busy / (hi - lo)))
    assert 0 < idle < 10
    assert h.reader("mfu")(t) == pytest.approx(100 * 4.0e9 * 12000 / 197e12)
    # the scope readers, on the executable's scope map recorded beside the
    # trace: each ms a step of its scopes' own device time, near what a
    # reading by hand of longer windows of the same program gave (chatglm3,
    # PERF.md section 5)
    got = {m: h.reader(m)(t) for m in ("attention_ms", "vocab_ms",
                                       "optimizer_ms")}
    by = rec["expect"]["scope_ms"]
    assert got == pytest.approx({
        "attention_ms": by["attn"],
        "vocab_ms": by["embed"] + by["logits"] + by["loss"],
        "optimizer_ms": by["optimizer"]}, rel=1e-12)
    assert got["attention_ms"] == pytest.approx(19.95, rel=0.05)
    assert got["vocab_ms"] == pytest.approx(36.80, rel=0.05)
    assert got["optimizer_ms"] == pytest.approx(32.62, rel=0.05)
    # every scope's time and the unscoped rest tile the ops' own time
    own = sum(o for _n, _iv, o, _k in trace.self_times(
        trace._ops(rec, trace.sorted_planes(rec)[0], lo, hi)))
    scoped = sum(v for v in by.values() if v) * t.n_steps * 1e6
    assert 0.85 * own < scoped < own
    # without a scope map the readers read nothing
    bare = SimpleNamespace(**dict(vars(t), rec=dict(rec, scopes=None)))
    assert all(h.reader(m)(bare) is None for m in got)


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
