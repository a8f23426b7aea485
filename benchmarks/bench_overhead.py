"""Table III analogue: tracer overhead.

ucTrace interposes at runtime (1.3x-25x slowdown, GB-scale logs).  Our trace
is compile-time: the overhead is pure offline analysis (HLO parse + assembly)
on top of an unavoidable lower+compile, with zero runtime cost.  We measure
lower/compile/parse wall time and trace size for a dense and a MoE step.

Also measures the analysis hot paths at the paper's experiment scale:

  * aggregation — a 100k-event trace rolled up by (kind x link) + semantic,
    columnar (`TraceStore` bincount) vs the per-event Python reference
    (>= 5x gate),
  * end-to-end ingest — parse -> attribute -> annotate -> store of a
    100k-site synthetic HLO module, single-pass columnar engine vs the
    per-event reference pipeline (>= 5x gate, byte-identical aggregates).
    The result is persisted to BENCH_ingest.json at the repo root so the
    perf trajectory is tracked across PRs, and
  * render + diff — JSON/HTML reports and a 3-way site-level session diff
    of a 100k-site trace, columnar emitters (`report` engine="columnar",
    `diff` union-vocab alignment) vs the per-event reference walk
    (engine="rows"), byte-identical output required (>= 5x gate).
    Persisted to BENCH_render.json at the repo root.
  * sharded single-module ingest — one giant multi-computation module
    split per-computation across spawn workers
    (`hlo_parser.parse_hlo_store_sharded` + `TraceStore.merge`) vs the
    serial columnar engine, merged store byte-identical required.  The
    2x speedup gate applies on boxes with >= 4 usable cores (parallel
    parse is CPU-bound; below that only the CI trajectory ratio gates).
    Persisted to BENCH_shard.json at the repo root.
  * append-mode ingest — one multi-computation module split into chunks,
    parsed and folded into a rolling store via `TraceStore.append` (the
    watch daemon's streaming path) vs one batch parse, appended store
    byte-identical required (>= 0.5x gate: chunking must stay within 2x
    of batch).  Persisted to BENCH_append.json at the repo root.
  * session persistence — save + load round-trip of a 2-trace session,
    compressed-npz columnar arrays vs compact JSON, exact round-trip
    required (the ratio is the size-independent trajectory signal).
    Persisted to BENCH_persist.json at the repo root.
  * warehouse tree merge — 256 per-host stores reduced via
    `TraceStore.merge_tree` (k-ary tree over a process pool) vs the
    serial left fold, result `identical` to the flat merge required.
    The 2x gate applies at >= 4 usable cores (mirrors BENCH_shard);
    the tree also wins algorithmically (O(n log n) vs O(n^2) row
    traffic), which is what single-core runs record.  Persisted to
    BENCH_merge.json at the repo root.
  * mmap zero-copy load — a fleet session opened eagerly vs
    `load(mmap=True)` on an uncompressed npz, gated on *peak RSS*
    (subprocess `ru_maxrss` deltas over an imports-only baseline), not
    wall clock: the mmap open must stay under an absolute ceiling and
    the eager/mmap RSS ratio is the trajectory signal; `query`/`diff`
    on a fleet slice must be byte-identical across the two load modes.
    Persisted to BENCH_mmapload.json at the repo root.

CI smoke entry points (no jax worker, smaller traces):

    python benchmarks/bench_overhead.py --ingest-only [--sites N]
    python benchmarks/bench_overhead.py --render-only [--sites N]
    python benchmarks/bench_overhead.py --shard-only [--sites N]
    python benchmarks/bench_overhead.py --append-only [--sites N]
    python benchmarks/bench_overhead.py --persist-only [--sites N]
    python benchmarks/bench_overhead.py --merge-only [--sites N]
    python benchmarks/bench_overhead.py --mmapload-only [--sites N]
"""
from __future__ import annotations

import json
import os
import time

from _util import REPO, run_worker

WORKER = """
import json
import time
import jax
import jax.numpy as jnp
from repro.configs import ARCHS, smoke_config
from repro.core import MeshSpec, trace_from_hlo
from repro.core.report import to_json
from repro.distributed import sharding as sh
from repro.distributed.autoshard import activation_sharding
from repro.launch.mesh import make_mesh
from repro.launch.presets import StepSettings
from repro.launch.steps import make_train_step
from repro.models import api
from repro.optim import adamw

mesh = make_mesh((2, 4), ("data", "model"))
spec = MeshSpec((2, 4), ("data", "model"))
rows = []
for arch in ("chatglm3-6b", "qwen3-moe-235b-a22b"):
    cfg = smoke_config(ARCHS[arch]).replace(
        d_model=128, d_ff=256, moe_d_ff=256 if ARCHS[arch].num_experts else 0,
        num_layers=8, vocab_size=512, num_heads=8, num_kv_heads=4, head_dim=16)
    st = StepSettings(accum=2, remat="full")
    step = make_train_step(cfg, adamw.AdamWConfig(), st)
    params = api.abstract_params(cfg)
    f32 = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32)
    opt = {"m": jax.tree.map(f32, params), "v": jax.tree.map(f32, params),
           "count": jax.ShapeDtypeStruct((), jnp.int32)}
    shape = type("S", (), {"global_batch": 8, "seq_len": 128, "kind": "train"})()
    batch = api.batch_specs(cfg, shape)
    pspecs = sh.param_pspecs(cfg, mesh)
    jfn = jax.jit(step, in_shardings=(
        sh.named(mesh, pspecs),
        sh.named(mesh, {"m": pspecs, "v": pspecs,
                        "count": jax.sharding.PartitionSpec()}), None),
        donate_argnums=(0, 1))
    t0 = time.perf_counter()
    with activation_sharding(mesh):
        lowered = jfn.lower(params, opt, batch)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    text = compiled.as_text()
    tr = trace_from_hlo(text, spec, label=arch,
                        cost_analysis=compiled.cost_analysis(),
                        memory_analysis=compiled.memory_analysis())
    t3 = time.perf_counter()
    js = to_json(tr)
    rows.append((f"overhead/{arch}/lower", (t1 - t0) * 1e6, "baseline-cost"))
    rows.append((f"overhead/{arch}/compile", (t2 - t1) * 1e6, "baseline-cost"))
    rows.append((f"overhead/{arch}/trace_parse", (t3 - t2) * 1e6,
                 f"overhead_ratio={(t3-t2)/max(t2-t0,1e-9):.3f}|"
                 f"hlo_KB={len(text)//1024}|trace_KB={len(js)//1024}|"
                 f"runtime_overhead=0x (compile-time tool)"))
print("JSON" + json.dumps(rows))
"""


def _write_bench_payload(stem: str, n_sites: int, payload: dict,
                         json_path: str = None) -> None:
    """Persist a bench payload: the repo-root artifact tracks the perf
    trajectory across PRs, so only full-size runs may write it (smoke
    sizes are not comparable and land in results/ instead).  Written
    atomically — the watch-daemon smoke job reads these mid-run."""
    from repro.core.persist import atomic_open
    if json_path is None:
        if n_sites >= 100_000:
            json_path = os.path.join(REPO, f"{stem}.json")
        else:
            os.makedirs(os.path.join(REPO, "results"), exist_ok=True)
            json_path = os.path.join(REPO, "results", f"{stem}_smoke.json")
    with atomic_open(json_path, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")


def _agg_100k_case(n_sites: int = 100_000, iters: int = 3):
    """Columnar vs per-event aggregation on a 100k-event synthetic trace."""
    from repro.core.synth import synthetic_trace
    from repro.core.topology import MeshSpec

    tr = synthetic_trace("agg100k", MeshSpec((2, 4), ("data", "model")),
                         n_sites=n_sites, seed=0)

    def legacy():
        a = tr.by(lambda e: f"{e.kind}|{e.link_class}")
        b = tr.by(lambda e: e.semantic or "other")
        return a, b

    def columnar():
        return tr.by_kind_and_link(), tr.by_semantic()

    t0 = time.perf_counter()
    build = tr.store                      # one-time column build, timed apart
    t_build = (time.perf_counter() - t0) * 1e6
    assert build.n == n_sites

    t0 = time.perf_counter()
    for _ in range(iters):
        ref = legacy()
    t_legacy = (time.perf_counter() - t0) / iters * 1e6

    t0 = time.perf_counter()
    for _ in range(iters):
        col = columnar()
    t_col = (time.perf_counter() - t0) / iters * 1e6

    # equivalence guard: same keys, same byte totals
    match = all(
        set(r) == set(c)
        and all(abs(r[k]["bytes"] - c[k]["bytes"]) < 1e-6 for k in r)
        for r, c in zip(ref, col))
    speedup = t_legacy / max(t_col, 1e-9)
    return [
        (f"overhead/agg{n_sites//1000}k/per_event", t_legacy, "baseline-cost"),
        (f"overhead/agg{n_sites//1000}k/columnar", t_col,
         f"speedup={speedup:.1f}x|target>=5x|sites={n_sites}|"
         f"store_build_us={t_build:.0f}|equivalent={match}"),
    ]


def _ingest_case(n_sites: int = 100_000, json_path: str = None):
    """End-to-end ingest: parse -> attribute -> annotate -> store, columnar
    engine vs per-event reference, with an exact-equality aggregate guard.

    Gate: >= 5x at 100k sites, batched aggregates byte-identical to the
    per-event reference path.
    """
    from repro.core.synth import synthetic_hlo
    from repro.core.topology import MeshSpec
    from repro.core.tracer import trace_from_hlo

    mesh = MeshSpec((2, 4), ("data", "model"))
    text = synthetic_hlo(n_sites=n_sites, seed=0)

    def aggregates(tr):
        return (tr.by_kind_and_link(), tr.by_semantic(),
                tr.total_collective_bytes(), tr.total_wire_bytes(),
                tr.total_est_time_s(), tr.overlapped_est_time_s())

    t0 = time.perf_counter()
    tr_ref = trace_from_hlo(text, mesh, label="ref", engine="rows")
    ref_aggs = aggregates(tr_ref)
    t_ref = time.perf_counter() - t0

    t0 = time.perf_counter()
    tr_fast = trace_from_hlo(text, mesh, label="fast", engine="columnar")
    fast_aggs = aggregates(tr_fast)
    t_fast = time.perf_counter() - t0

    sites = tr_fast.sites
    # equivalence guard: byte-identical aggregates (exact ==, no tolerance)
    equivalent = (sites == tr_ref.sites and ref_aggs == fast_aggs)
    speedup = t_ref / max(t_fast, 1e-9)
    payload = {
        "bench": "ingest_e2e",
        "sites": sites,
        "hlo_kb": len(text) // 1024,
        "ref_s": round(t_ref, 4),
        "columnar_s": round(t_fast, 4),
        "ref_events_per_sec": round(sites / max(t_ref, 1e-9)),
        "columnar_events_per_sec": round(sites / max(t_fast, 1e-9)),
        "speedup": round(speedup, 2),
        "target": 5.0,
        "equivalent": equivalent,
    }
    _write_bench_payload("BENCH_ingest", n_sites, payload, json_path)
    rows = [
        (f"overhead/ingest{n_sites//1000}k/per_event", t_ref * 1e6,
         "baseline-cost"),
        (f"overhead/ingest{n_sites//1000}k/columnar", t_fast * 1e6,
         f"speedup={speedup:.1f}x|target>=5x|sites={sites}|"
         f"events_per_sec={payload['columnar_events_per_sec']}|"
         f"equivalent={equivalent}"),
    ]
    return rows, payload


def _render_case(n_sites: int = 100_000, json_path: str = None):
    """Renderer + diff: columnar emitters vs the per-event reference.

    Workload: JSON report, HTML report, a 3-trace site-level `diff_n`,
    and a pairwise `diff_traces` — once with engine="rows" (per-event
    walks, dict-aligned diff), once columnar.  Gate: >= 5x at 100k sites
    with byte-identical renderer output and identical diff rows; the
    streaming `write_json` must reproduce `to_json` exactly.
    """
    import io

    from repro.core import diff as diff_mod
    from repro.core import report as report_mod
    from repro.core.synth import synthetic_trace
    from repro.core.topology import MeshSpec

    mesh = MeshSpec((2, 4), ("data", "model"))
    traces = [
        synthetic_trace("base", mesh, n_sites=n_sites, seed=0),
        synthetic_trace("dp-heavy", mesh, n_sites=n_sites, seed=1,
                        axis_weights=(3.0, 1.0)),
        synthetic_trace("tp-heavy", mesh, n_sites=n_sites, seed=2,
                        axis_weights=(1.0, 3.0)),
    ]
    tr = traces[0]
    for t in traces:        # materialize both views outside the timing
        _ = t.events, t.store

    def render(engine):
        return (report_mod.to_json(tr, engine=engine),
                report_mod.to_html(tr, mesh, engine=engine),
                diff_mod.diff_n(traces, by="site", engine=engine),
                diff_mod.diff_traces(traces[0], traces[1], by="kind_link",
                                     engine=engine))

    t0 = time.perf_counter()
    ref = render("rows")
    t_ref = time.perf_counter() - t0

    t0 = time.perf_counter()
    fast = render("columnar")
    t_fast = time.perf_counter() - t0

    buf = io.StringIO()
    report_mod.write_json(tr, buf, chunk_sites=max(n_sites // 4, 1))
    identical = (ref[0] == fast[0] and ref[1] == fast[1]
                 and ref[2] == fast[2] and ref[3] == fast[3]
                 and buf.getvalue() == fast[0])
    speedup = t_ref / max(t_fast, 1e-9)
    payload = {
        "bench": "render_diff",
        "sites": n_sites,
        "n_traces": len(traces),
        "json_kb": len(fast[0]) // 1024,
        "ref_s": round(t_ref, 4),
        "columnar_s": round(t_fast, 4),
        "speedup": round(speedup, 2),
        "target": 5.0,
        "byte_identical": identical,
    }
    _write_bench_payload("BENCH_render", n_sites, payload, json_path)
    rows = [
        (f"overhead/render{n_sites//1000}k/per_event", t_ref * 1e6,
         "baseline-cost"),
        (f"overhead/render{n_sites//1000}k/columnar", t_fast * 1e6,
         f"speedup={speedup:.1f}x|target>=5x|sites={n_sites}|"
         f"json_kb={payload['json_kb']}|byte_identical={identical}"),
    ]
    return rows, payload


def _shard_case(n_sites: int = 100_000, json_path: str = None):
    """Sharded single-module ingest vs the serial columnar engine.

    One synthetic multi-computation module (the 405B-dump shape: many
    `%stage<k>` computations plus a while body) parses once serially and
    once split per-computation across workers, with the merged store
    required byte-identical (`TraceStore.identical`) to the serial one.

    Gate: >= 2x at 100k sites *when the box has >= 4 usable cores*
    (`gate_applies` in the payload) — the sharded path is CPU-bound
    parallel parse, so 2-core runners physically cap below 2x and rely
    on the CI trajectory ratio instead.
    """
    import dataclasses

    from repro.core import hlo_parser
    from repro.core.synth import synthetic_hlo
    from repro.core.topology import MeshSpec
    from repro.core.tracer import trace_from_hlo

    mesh = MeshSpec((2, 4), ("data", "model"))
    text = synthetic_hlo(n_sites=n_sites, seed=0, n_computations=64)
    shards = max(hlo_parser.auto_shards(len(text)), 2)
    usable = min(shards, os.cpu_count() or 1)

    t0 = time.perf_counter()
    tr_serial = trace_from_hlo(text, mesh, label="serial", shards=1)
    t_serial = time.perf_counter() - t0

    t0 = time.perf_counter()
    tr_shard = trace_from_hlo(text, mesh, label="sharded", shards=shards)
    t_shard = time.perf_counter() - t0

    def stats_match(a: dict, b: dict) -> bool:
        # int fields exact; float stats within 1e-9 relative — the shard
        # partial sums reassociate additions, which is exact only while
        # the integer-valued totals stay below 2^53 (a 405B-class dump
        # can exceed that without the parse being wrong)
        for key, va in a.items():
            vb = b[key]
            if isinstance(va, dict):
                if set(va) != set(vb) or any(
                        abs(va[s] - vb[s]) > 1e-9 * max(abs(va[s]), 1.0)
                        for s in va):
                    return False
            elif isinstance(va, float):
                if abs(va - vb) > 1e-9 * max(abs(va), 1.0):
                    return False
            elif va != vb:
                return False
        return True

    identical = (
        tr_shard.store.identical(tr_serial.store)
        and stats_match(dataclasses.asdict(tr_shard.op_stats),
                        dataclasses.asdict(tr_serial.op_stats))
        and tr_shard.by_kind_and_link() == tr_serial.by_kind_and_link()
        and tr_shard.total_est_time_s() == tr_serial.total_est_time_s())
    speedup = t_serial / max(t_shard, 1e-9)
    payload = {
        "bench": "shard_ingest",
        "sites": tr_shard.sites,
        "hlo_kb": len(text) // 1024,
        "shards": shards,
        "usable_cores": usable,
        "serial_s": round(t_serial, 4),
        "sharded_s": round(t_shard, 4),
        "speedup": round(speedup, 2),
        "target": 2.0,
        "gate_applies": usable >= 4 and n_sites >= 100_000,
        "byte_identical": identical,
    }
    _write_bench_payload("BENCH_shard", n_sites, payload, json_path)
    rows = [
        (f"overhead/shard{n_sites//1000}k/serial", t_serial * 1e6,
         "baseline-cost"),
        (f"overhead/shard{n_sites//1000}k/sharded", t_shard * 1e6,
         f"speedup={speedup:.2f}x|target>=2x@4cores|shards={shards}|"
         f"usable_cores={usable}|byte_identical={identical}"),
    ]
    return rows, payload


def _append_case(n_sites: int = 100_000, n_chunks: int = 16,
                 json_path: str = None):
    """Streaming append-mode ingest vs one batch parse.

    One multi-computation module splits into `n_chunks` per-computation
    chunks (the watch daemon's arrival order); each chunk parses and
    folds into a rolling store via `TraceStore.append`.  The appended
    store must be byte-identical (`TraceStore.identical`) to the batch
    `parse_hlo_store` of the whole text — the live-profiling invariant.

    Gate: >= 0.5x of the batch parse — amortized-doubling buffers and
    cached interning keep the chunked path within 2x of batch despite
    paying per-chunk parser overhead N times; a super-linear append
    (re-copying columns per chunk) collapses this ratio.
    """
    from repro.core import hlo_parser
    from repro.core.store import IncrementalRollup, TraceStore
    from repro.core.synth import synthetic_hlo

    mesh_devices = 8
    text = synthetic_hlo(n_sites=n_sites, seed=0, n_computations=64)
    chunks, ctx = hlo_parser.split_hlo_module(text, n_chunks)

    t0 = time.perf_counter()
    batch, _ = hlo_parser.parse_hlo_store(text, mesh_devices)
    t_batch = time.perf_counter() - t0

    t0 = time.perf_counter()
    acc = TraceStore.empty()
    roll = IncrementalRollup("kind_link")
    for c in chunks:
        store, _ = hlo_parser.parse_hlo_store(c, mesh_devices,
                                              shard_ctx=ctx)
        acc.append(store)
        roll.update(store)
    t_append = time.perf_counter() - t0

    identical = acc.identical(batch) and len(roll.labels) > 0
    speedup = t_batch / max(t_append, 1e-9)
    payload = {
        "bench": "append_ingest",
        "sites": acc.n,
        "hlo_kb": len(text) // 1024,
        "chunks": len(chunks),
        "batch_s": round(t_batch, 4),
        "append_s": round(t_append, 4),
        "speedup": round(speedup, 2),
        "target": 0.5,
        "byte_identical": identical,
    }
    _write_bench_payload("BENCH_append", n_sites, payload, json_path)
    rows = [
        (f"overhead/append{n_sites//1000}k/batch_parse", t_batch * 1e6,
         "baseline-cost"),
        (f"overhead/append{n_sites//1000}k/chunked_append", t_append * 1e6,
         f"speedup={speedup:.2f}x|target>=0.5x|chunks={len(chunks)}|"
         f"byte_identical={identical}"),
    ]
    return rows, payload


def _persist_case(n_sites: int = 100_000, json_path: str = None):
    """Session save/load round-trip: compressed npz vs compact JSON.

    Both formats must round-trip the columnar stores *exactly*
    (`TraceStore.identical`); the gated number is the npz/JSON
    round-trip ratio — roughly size-independent, so the smoke run
    tracks the committed trajectory, and an npz serialization
    regression drops it below the CI ratio gate.
    """
    import tempfile

    from repro.core.session import TraceSession
    from repro.core.synth import synthetic_trace
    from repro.core.topology import MeshSpec

    mesh = MeshSpec((2, 4), ("data", "model"))
    sess = TraceSession("persist", [
        synthetic_trace("a", mesh, n_sites=n_sites, seed=0),
        synthetic_trace("b", mesh, n_sites=n_sites, seed=1,
                        axis_weights=(3.0, 1.0)),
    ])
    for t in sess:                      # build stores outside the timing
        _ = t.store

    with tempfile.TemporaryDirectory() as td:
        jp = os.path.join(td, "sess.json")
        zp = os.path.join(td, "sess.npz")
        t0 = time.perf_counter()
        sess.save(jp)
        loaded_json = TraceSession.load(jp)
        t_json = time.perf_counter() - t0
        t0 = time.perf_counter()
        sess.save(zp)
        loaded_npz = TraceSession.load(zp)
        t_npz = time.perf_counter() - t0
        json_kb = os.path.getsize(jp) // 1024
        npz_kb = os.path.getsize(zp) // 1024

    def same(loaded):
        return (loaded.labels() == sess.labels() and all(
            a.store.identical(b.store)
            and a.total_est_time_s() == b.total_est_time_s()
            for a, b in zip(sess, loaded)))

    round_trip_ok = same(loaded_json) and same(loaded_npz)
    speedup = t_json / max(t_npz, 1e-9)
    payload = {
        "bench": "session_persist",
        "sites": n_sites,
        "n_traces": len(sess),
        "json_kb": json_kb,
        "npz_kb": npz_kb,
        "json_s": round(t_json, 4),
        "npz_s": round(t_npz, 4),
        "speedup": round(speedup, 2),
        "target": 1.0,
        "round_trip_ok": round_trip_ok,
    }
    _write_bench_payload("BENCH_persist", n_sites, payload, json_path)
    rows = [
        (f"overhead/persist{n_sites//1000}k/json_roundtrip", t_json * 1e6,
         "baseline-cost"),
        (f"overhead/persist{n_sites//1000}k/npz_roundtrip", t_npz * 1e6,
         f"speedup={speedup:.2f}x|target>=1x|json_kb={json_kb}|"
         f"npz_kb={npz_kb}|round_trip_ok={round_trip_ok}"),
    ]
    return rows, payload


def _merge_case(n_sites: int = 100_000, n_stores: int = 256,
                json_path: str = None):
    """Warehouse tree-reduction merge vs the serial left fold.

    `n_stores` per-host stores (distinct-seed synthetic modules, cycled
    so setup stays parse-light) reduce two ways: the O(n^2)-row-traffic
    left fold (`acc = merge([acc, s])`, the naive warehouse loop) and
    `TraceStore.merge_tree` (k-ary, process pool when cores allow).
    Both must be `identical` to the flat `TraceStore.merge` — the
    associativity invariant the query layer leans on.

    Gate: >= 2x over the fold at >= 4 usable cores (BENCH_shard's core
    guard); below that the run still records the algorithmic win —
    tree depth log_k(n) copies each row O(log n) times vs the fold's
    O(n) — which is why single-core smoke ratios stay meaningful.
    """
    from repro.core import hlo_parser
    from repro.core.store import TraceStore
    from repro.core.synth import synthetic_hlo

    per = max(n_sites // n_stores, 1)
    base = []
    for seed in range(min(n_stores, 16)):
        text = synthetic_hlo(n_sites=per, seed=seed, n_computations=1)
        store, _ = hlo_parser.parse_hlo_store(text, 8)
        base.append(store)
    stores = [base[i % len(base)] for i in range(n_stores)]
    usable = min(os.cpu_count() or 1, 8)

    flat = TraceStore.merge(stores)

    t0 = time.perf_counter()
    acc = stores[0]
    for s in stores[1:]:
        acc = TraceStore.merge([acc, s])
    t_fold = time.perf_counter() - t0

    t0 = time.perf_counter()
    tree = TraceStore.merge_tree(stores, arity=8, workers=usable)
    t_tree = time.perf_counter() - t0

    identical = tree.identical(flat) and acc.identical(flat)
    speedup = t_fold / max(t_tree, 1e-9)
    payload = {
        "bench": "merge_tree",
        "sites": flat.n,
        "stores": n_stores,
        "arity": 8,
        "usable_cores": usable,
        "fold_s": round(t_fold, 4),
        "tree_s": round(t_tree, 4),
        "speedup": round(speedup, 2),
        "target": 2.0,
        "gate_applies": usable >= 4 and n_sites >= 100_000,
        "byte_identical": identical,
    }
    _write_bench_payload("BENCH_merge", n_sites, payload, json_path)
    rows = [
        (f"overhead/merge{n_sites//1000}k/serial_fold", t_fold * 1e6,
         "baseline-cost"),
        (f"overhead/merge{n_sites//1000}k/tree_reduce", t_tree * 1e6,
         f"speedup={speedup:.2f}x|target>=2x@4cores|stores={n_stores}|"
         f"usable_cores={usable}|byte_identical={identical}"),
    ]
    return rows, payload


# Runs once per load mode in a child interpreter so the RSS high-water
# mark isolates that mode's footprint; mode "base" stops after the
# imports and prices the interpreter + numpy baseline the deltas
# subtract out.  Forked children inherit the parent's peak RSS (the
# bench parent holds the whole fleet session), so the worker resets
# its high-water mark to current RSS (`clear_refs`) after the imports
# and reads `VmHWM` — `ru_maxrss` is the fallback where /proc is
# missing, with the base subtraction absorbing the inherited peak.
_MMAP_WORKER = """
import json, resource, sys
mode, path = sys.argv[1], sys.argv[2]
from repro.core.session import TraceSession

def peak_kb():
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmHWM:"):
                    return int(line.split()[1])
    except OSError:
        pass
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss

try:
    with open("/proc/self/clear_refs", "w") as f:
        f.write("5")
except OSError:
    pass
out = {"query": None, "diff": None}
if mode != "base":
    sess = TraceSession.load(path, mmap=(mode == "mmap"))
    out["query"] = json.dumps(sess.query(host="00*", by="kind_link"),
                              sort_keys=True)
    out["diff"] = sess.diff("host=000", "host=001", as_json=True)
out["rss_kb"] = peak_kb()
print("JSON" + json.dumps(out))
"""


def _mmapload_case(n_sites: int = 1_000_000, json_path: str = None):
    """Eager vs memory-mapped fleet-session load, gated on peak RSS.

    An 8-host fleet session (`n_sites` total) is saved uncompressed,
    then three child interpreters report `ru_maxrss`: imports-only
    (base), eager `load`, and `load(mmap=True)` — each also running the
    same fleet `query` + slice `diff`.  Deltas over base make the
    numbers machine-portable; the gates are (1) the mmap delta under an
    absolute ceiling (`max(64MB, 200B/site)` — a materialized load
    costs ~160B/site in columns alone, so a leaky mmap path cannot
    hide), and (2) query/diff output byte-identical across load modes.
    The eager/mmap delta ratio is the CI trajectory `speedup`.
    """
    import subprocess
    import sys
    import tempfile

    from repro.core.session import TraceSession
    from repro.core.synth import synthetic_trace
    from repro.core.topology import MeshSpec

    n_hosts = 8
    per = max(n_sites // n_hosts, 1)
    mesh = MeshSpec((2, 4), ("data", "model"))
    sess = TraceSession("mmapfleet", [
        synthetic_trace(f"host{h:03d}_step000", mesh, n_sites=per, seed=h)
        for h in range(n_hosts)])
    for t in sess:
        _ = t.store

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") \
        + os.pathsep + env.get("PYTHONPATH", "")

    def probe(mode, path):
        t0 = time.perf_counter()
        proc = subprocess.run(
            [sys.executable, "-c", _MMAP_WORKER, mode, path],
            capture_output=True, text=True, env=env, check=True)
        dt = time.perf_counter() - t0
        for line in proc.stdout.splitlines():
            if line.startswith("JSON"):
                return json.loads(line[4:]), dt
        raise RuntimeError(f"no JSON output from {mode} worker:\n"
                           + proc.stderr)

    with tempfile.TemporaryDirectory() as td:
        zp = os.path.join(td, "fleet.npz")
        sess.save(zp, compress=False)
        npz_mb = os.path.getsize(zp) / 1e6
        base, _ = probe("base", zp)
        eager, t_eager = probe("eager", zp)
        mmap_, t_mmap = probe("mmap", zp)

    # floor both deltas at 1MB: tiny smoke runs otherwise divide page
    # noise by page noise and the trajectory ratio loses its meaning
    eager_delta = max(eager["rss_kb"] - base["rss_kb"], 1024) / 1024.0
    mmap_delta = max(mmap_["rss_kb"] - base["rss_kb"], 1024) / 1024.0
    ceiling_mb = max(64.0, n_sites * 200 / 1e6)
    under_ceiling = mmap_delta <= ceiling_mb
    byte_identical = (eager["query"] == mmap_["query"]
                      and eager["diff"] == mmap_["diff"]
                      and eager["query"] is not None)
    speedup = eager_delta / mmap_delta
    payload = {
        "bench": "mmap_load",
        "sites": n_hosts * per,
        "n_traces": n_hosts,
        "npz_mb": round(npz_mb, 1),
        "rss_base_mb": round(base["rss_kb"] / 1024.0, 1),
        "eager_delta_mb": round(eager_delta, 1),
        "mmap_delta_mb": round(mmap_delta, 1),
        "rss_ceiling_mb": round(ceiling_mb, 1),
        "rss_under_ceiling": under_ceiling,
        "byte_identical": byte_identical,
        "speedup": round(speedup, 2),
        "target": 2.0,
        "gate_applies": n_sites >= 100_000,
        "ok": under_ceiling and byte_identical,
    }
    _write_bench_payload("BENCH_mmapload", n_sites, payload, json_path)
    rows = [
        (f"overhead/mmap{n_sites//1000}k/eager_load", t_eager * 1e6,
         f"rss_delta_mb={eager_delta:.1f}|baseline-cost"),
        (f"overhead/mmap{n_sites//1000}k/mmap_load", t_mmap * 1e6,
         f"rss_delta_mb={mmap_delta:.1f}|rss_ratio={speedup:.2f}x|"
         f"ceiling_mb={ceiling_mb:.0f}|under_ceiling={under_ceiling}|"
         f"byte_identical={byte_identical}"),
    ]
    return rows, payload


def run():
    rows = _agg_100k_case()
    render_rows, _rpayload = _render_case()     # 100k: writes BENCH_render.json
    rows += render_rows
    ingest_rows, _payload = _ingest_case()      # 100k: writes BENCH_ingest.json
    rows += ingest_rows
    shard_rows, _spayload = _shard_case()       # 100k: writes BENCH_shard.json
    rows += shard_rows
    append_rows, _apayload = _append_case()     # 100k: BENCH_append.json
    rows += append_rows
    persist_rows, _ppayload = _persist_case()   # 100k: BENCH_persist.json
    rows += persist_rows
    merge_rows, _mpayload = _merge_case()       # 100k: BENCH_merge.json
    rows += merge_rows
    mmap_rows, _mmpayload = _mmapload_case()    # 1M: BENCH_mmapload.json
    rows += mmap_rows
    out = run_worker(WORKER, devices=8)
    for line in out.splitlines():
        if line.startswith("JSON"):
            return rows + [tuple(r) for r in json.loads(line[4:])]
    raise RuntimeError("no JSON output from worker")


if __name__ == "__main__":
    # smoke entry points for CI: the ingest and/or render cases only (pure
    # numpy, no jax compile workers), with a configurable trace size.
    import argparse
    import sys

    sys.path.insert(0, os.path.join(REPO, "src"))

    ap = argparse.ArgumentParser()
    ap.add_argument("--ingest-only", action="store_true")
    ap.add_argument("--render-only", action="store_true")
    ap.add_argument("--shard-only", action="store_true")
    ap.add_argument("--append-only", action="store_true")
    ap.add_argument("--persist-only", action="store_true")
    ap.add_argument("--merge-only", action="store_true")
    ap.add_argument("--mmapload-only", action="store_true")
    ap.add_argument("--sites", type=int,
                    default=int(os.environ.get("INGEST_SITES", 100_000)))
    args = ap.parse_args()
    if not (args.ingest_only or args.render_only or args.shard_only
            or args.append_only or args.persist_only or args.merge_only
            or args.mmapload_only):
        ap.error("pass --ingest-only / --render-only / --shard-only / "
                 "--append-only / --persist-only / --merge-only / "
                 "--mmapload-only as a direct entry point")
    cases = [
        # (enabled, case fn, artifact stem, equivalence key, label)
        (args.ingest_only, _ingest_case, "BENCH_ingest", "equivalent",
         "ingest"),
        (args.render_only, _render_case, "BENCH_render", "byte_identical",
         "render"),
        (args.shard_only, _shard_case, "BENCH_shard", "byte_identical",
         "shard"),
        (args.append_only, _append_case, "BENCH_append", "byte_identical",
         "append"),
        (args.persist_only, _persist_case, "BENCH_persist", "round_trip_ok",
         "persist"),
        (args.merge_only, _merge_case, "BENCH_merge", "byte_identical",
         "merge"),
        (args.mmapload_only, _mmapload_case, "BENCH_mmapload", "ok",
         "mmapload"),
    ]
    failed = False
    for enabled, case_fn, stem, equiv_key, label in cases:
        if not enabled:
            continue
        rows, payload = case_fn(n_sites=args.sites)
        dest = f"{stem}.json" if args.sites >= 100_000 \
            else f"results/{stem}_smoke.json"
        for name, us, derived in rows:
            print(f"{name},{us:.2f},{derived}")
        gate_applies = payload.get("gate_applies", args.sites >= 100_000)
        if not payload[equiv_key]:
            print(f"FAIL: {label} output diverges from its reference "
                  "engine", file=sys.stderr)
            failed = True
        elif payload["speedup"] < payload["target"] and gate_applies:
            print(f"FAIL: {label} speedup {payload['speedup']}x below the "
                  f"{payload['target']}x gate", file=sys.stderr)
            failed = True
        else:
            print(f"{label} ok: {payload['speedup']}x at {payload['sites']} "
                  f"sites -> {dest}")
    sys.exit(1 if failed else 0)
