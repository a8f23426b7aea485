"""Ingest-pipeline equivalence: columnar fast path vs per-event reference.

The batched cost model (`costmodel.annotate_store`), vocab-level
attribution (`attribution.attribute_store`), and single-pass parser
(`hlo_parser.parse_hlo_store`) must match the per-event reference path
(`annotate_event` / `attribute_event` / `parse_hlo`) field-for-field on
randomized synthetic HLO with duplicated op_names and mixed iota/explicit
replica groups.
"""
import dataclasses

import pytest

from _hypothesis_compat import given, settings, strategies as st
from repro.core import attribution, costmodel, hlo_parser
from repro.core.store import TraceStore
from repro.core.synth import synthetic_hlo, synthetic_trace
from repro.core.topology import MeshSpec, V5E, resolve_iota_groups
from repro.core.tracer import trace_from_hlo

MESH = MeshSpec((2, 4), ("data", "model"))


def ingest_pair(seed: int, n_sites: int = 400, trip_count: int = 12):
    text = synthetic_hlo(n_sites=n_sites, seed=seed, trip_count=trip_count)
    ref = trace_from_hlo(text, MESH, label="ref", engine="rows")
    fast = trace_from_hlo(text, MESH, label="fast", engine="columnar")
    return ref, fast


# -- end-to-end: parse -> annotate -> attribute -> store --------------------

@given(seed=st.integers(0, 1000))
@settings(max_examples=8, deadline=None)
def test_ingest_rows_match_reference(seed):
    """Every materialized row of the columnar ingest equals the reference
    `CollectiveEvent`, field for field."""
    ref, fast = ingest_pair(seed)
    er, ef = ref.events, fast.events
    assert len(er) == len(ef) and len(er) > 0
    for a, b in zip(er, ef):
        if a != b:   # narrow the failure to the diverging field
            for fld in dataclasses.fields(a):
                assert getattr(a, fld.name) == getattr(b, fld.name), fld.name
        assert a == b


@given(seed=st.integers(0, 1000))
@settings(max_examples=8, deadline=None)
def test_ingest_aggregates_byte_identical(seed):
    ref, fast = ingest_pair(seed)
    assert ref.by_kind_and_link() == fast.by_kind_and_link()
    assert ref.by_semantic() == fast.by_semantic()
    assert ref.store.by_sem_kind_link() == fast.store.by_sem_kind_link()
    assert ref.total_collective_bytes() == fast.total_collective_bytes()
    assert ref.total_wire_bytes() == fast.total_wire_bytes()
    assert ref.total_est_time_s() == fast.total_est_time_s()
    assert ref.overlapped_est_time_s() == fast.overlapped_est_time_s()


def test_ingest_op_stats_identical():
    ref, fast = ingest_pair(3)
    assert dataclasses.asdict(ref.op_stats) == dataclasses.asdict(fast.op_stats)


def test_ingest_comm_matrix_identical():
    import numpy as np

    from repro.core.topology import comm_matrix
    ref, fast = ingest_pair(5)
    np.testing.assert_allclose(comm_matrix(MESH, fast),
                               comm_matrix(MESH, list(ref.events)),
                               rtol=1e-12)


# -- batched annotate/attribute over an existing store ----------------------

@given(seed=st.integers(0, 500))
@settings(max_examples=6, deadline=None)
def test_annotate_store_matches_annotate_event(seed):
    """`annotate_store` + `attribute_store` on a store whose derived fields
    were wiped reproduces the per-event pipeline exactly."""
    tr = synthetic_trace(f"s{seed}", MESH, n_sites=300, seed=seed)
    ref_rows = tr.events
    store = TraceStore.from_events(ref_rows)
    # wipe the derived columns, then re-derive through the batched path
    from repro.core.store import Categorical
    n = store.n
    store.link_class = Categorical.constant(n)
    store.semantic = Categorical.constant(n)
    store.protocol = Categorical.constant(n)
    store.scope = Categorical.constant(n)
    store.jax_prim = Categorical.constant(n)
    store.wire_bytes_per_device = store.wire_bytes_per_device * 0.0
    store.est_time_s = store.est_time_s * 0.0
    costmodel.annotate_store(store, MESH, V5E)
    attribution.attribute_store(store)
    assert store.rows() == ref_rows


def test_parse_hlo_store_matches_parse_hlo():
    text = synthetic_hlo(n_sites=200, seed=9)
    events, stats = hlo_parser.parse_hlo(text, MESH.num_devices)
    store, fstats = hlo_parser.parse_hlo_store(text, MESH.num_devices)
    assert dataclasses.asdict(stats) == dataclasses.asdict(fstats)
    assert store.n == len(events)
    # parser-level fields (derived fields are blank on both sides here)
    for ev, row in zip(events, store.rows()):
        assert (ev.name, ev.kind, ev.async_start, ev.operand_bytes,
                ev.result_bytes, ev.dtype, ev.replica_groups, ev.group_size,
                ev.num_groups, ev.op_name, ev.computation, ev.multiplicity,
                ev.channel_id, ev.source_target_pairs) == \
               (row.name, row.kind, row.async_start, row.operand_bytes,
                row.result_bytes, row.dtype, row.replica_groups,
                row.group_size, row.num_groups, row.op_name, row.computation,
                row.multiplicity, row.channel_id, row.source_target_pairs)


def test_ingest_empty_module():
    text = "HloModule empty\n\nENTRY %main (x: f32[4]) -> f32[4] {\n" \
           "  %x = f32[4] parameter(0)\n  ROOT %y = f32[4] copy(%x)\n}\n"
    tr = trace_from_hlo(text, MESH, engine="columnar")
    assert tr.sites == 0
    assert tr.by_kind_and_link() == {}
    assert tr.by_semantic() == {}
    assert tr.events == []


def test_ingest_identical_when_first_site_is_rendezvous():
    """The columnar store interns `protocol` in first-seen order, as the
    per-event path does: a module whose first collective is above the
    rendezvous threshold (as in a compiled TPU train step) still gives
    identical stores."""
    def site(i, shape):
        return (f"  %ar{i} = f32[{shape}] all-reduce(%p{i}), channel_id={i},"
                f" replica_groups=[2,4]<=[8], to_apply=%add, "
                f"metadata={{op_name=\"jit(f)/psum{i}\"}}\n")
    text = ("HloModule rndv_first\n\n"
            "%add (a: f32[], b: f32[]) -> f32[] {\n"
            "  %a = f32[] parameter(0)\n  %b = f32[] parameter(1)\n"
            "  ROOT %r = f32[] add(%a, %b)\n}\n\n"
            "ENTRY %main (p1: f32[1024,1024], p2: f32[4]) -> f32[4] {\n"
            "  %p1 = f32[1024,1024] parameter(0)\n"
            "  %p2 = f32[4] parameter(1)\n"
            + site(1, "1024,1024") + site(2, "4") +
            "  ROOT %out = f32[4] copy(%ar2)\n}\n")
    rows = trace_from_hlo(text, MESH, engine="rows")
    fast = trace_from_hlo(text, MESH, engine="columnar")
    assert [e.protocol for e in rows.events] == ["rndv", "eager"]
    assert fast.store.identical(rows.store)


# -- payload dedup + memoization --------------------------------------------

def test_store_payload_dedup():
    """Repeated replica-group attrs collapse into a handful of tables."""
    _ref, fast = ingest_pair(1, n_sites=500)
    s = fast.store
    assert s.n == 500
    assert len(s.group_tables) <= 10       # 7 rg attrs + default
    assert len(s.stp_tables) <= 2
    assert len(s.op_name.vocab) < 100      # heavy duplication preserved
    # per-row compatibility views still line up
    assert len(s.replica_groups) == s.n
    assert len(s.axes) == s.n
    assert len(s.op_names) == s.n


def test_resolve_iota_groups_memoized():
    a = resolve_iota_groups(2, 4, [8], None)
    b = resolve_iota_groups(2, 4, (8,), None)
    assert a == b == [[0, 1, 2, 3], [4, 5, 6, 7]]
    assert a is not b                      # lists are fresh (mutation-safe)
    b[0][0] = 99
    assert resolve_iota_groups(2, 4, [8], None)[0][0] == 0
    from repro.core.topology import _resolve_iota_cached
    assert _resolve_iota_cached.cache_info().hits >= 2


def test_resolve_iota_transposed_expansions_pinned():
    # [4,2]<=[2,4]T(1,0): column-major walk of the 2x4 grid -> stride-4 pairs
    assert resolve_iota_groups(4, 2, [2, 4], (1, 0)) == \
        [[0, 4], [1, 5], [2, 6], [3, 7]]
    # [2,4]<=[4,2]T(1,0): stride-2 interleave
    assert resolve_iota_groups(2, 4, [4, 2], (1, 0)) == \
        [[0, 2, 4, 6], [1, 3, 5, 7]]
    # identity transpose matches the plain form
    assert resolve_iota_groups(2, 4, [2, 4], (0, 1)) == \
        resolve_iota_groups(2, 4, [8], None)


def test_resolve_iota_malformed_raises():
    with pytest.raises(ValueError, match="prod"):
        resolve_iota_groups(3, 3, [8], None)          # 3*3 != 8
    with pytest.raises(ValueError, match="transpose"):
        resolve_iota_groups(4, 2, [2, 4], (0, 2))     # bad permutation


def _one_site_hlo(rg_attr: str) -> str:
    return (
        "HloModule malformed\n\n"
        "%add (a: f32[], b: f32[]) -> f32[] {\n"
        "  %a = f32[] parameter(0)\n"
        "  %b = f32[] parameter(1)\n"
        "  ROOT %r = f32[] add(%a, %b)\n"
        "}\n\n"
        "ENTRY %main (x: f32[128,128]) -> f32[128,128] {\n"
        "  %x = f32[128,128] parameter(0)\n"
        f"  %all-reduce.1 = f32[128,128] all-reduce(%x), channel_id=1, "
        f"{rg_attr}, to_apply=%add, "
        "metadata={op_name=\"jit(f)/psum\"}\n"
        "  ROOT %out = f32[128,128] add(%all-reduce.1, %x)\n"
        "}\n")


@pytest.mark.parametrize("rg_attr", [
    "replica_groups=[3,3]<=[8]",              # count*size != prod(dims)
    "replica_groups=[4,2]<=[2,4]T(0,2)",      # invalid transpose perm
    "replica_groups={}",                      # empty form
])
def test_malformed_iota_falls_back_full_range(rg_attr):
    """Both parser engines degrade malformed/empty replica_groups to the
    single full-range group instead of crashing mid-module."""
    text = _one_site_hlo(rg_attr)
    events, _ = hlo_parser.parse_hlo(text, MESH.num_devices)
    store, _ = hlo_parser.parse_hlo_store(text, MESH.num_devices)
    full = [list(range(MESH.num_devices))]
    assert [e.replica_groups for e in events] == [full]
    assert store.replica_groups == [full]


# -- store schema round-trip (v2) + v1 compat --------------------------------

def test_store_v2_roundtrip_after_fast_ingest():
    import json
    _ref, fast = ingest_pair(7, n_sites=150)
    d = json.loads(json.dumps(fast.store.to_dict()))
    assert d["version"] == 2
    store2 = TraceStore.from_dict(d)
    assert store2.rows() == fast.store.rows()


def test_store_v1_dict_still_loads():
    tr = synthetic_trace("v1", MESH, n_sites=60, seed=2)
    store = tr.store
    d = store.to_dict()
    # down-convert to the v1 layout (per-row payloads)
    v1 = {k: d[k] for k in ("n", "num")}
    v1["version"] = 1
    v1["cat"] = {k: v for k, v in d["cat"].items() if k != "op_name"}
    v1["names"] = store.names
    v1["op_names"] = store.op_names
    v1["axes"] = [list(a) for a in store.axes]
    v1["replica_groups"] = store.replica_groups
    v1["source_target_pairs"] = [
        None if p is None else [list(pair) for pair in p]
        for p in store.source_target_pairs]
    store2 = TraceStore.from_dict(v1)
    assert store2.rows() == store.rows()


# -- parallel multi-file session ingest --------------------------------------

@pytest.mark.parametrize("workers", [1, 2])
def test_session_from_hlo(workers):
    from repro.core.session import TraceSession
    items = [(f"cfg{i}", synthetic_hlo(n_sites=80, seed=i)) for i in range(3)]
    sess = TraceSession.from_hlo("sweep", items, MESH, max_workers=workers)
    assert sess.labels() == ["cfg0", "cfg1", "cfg2"]
    for (label, text), tr in zip(items, sess):
        ref = trace_from_hlo(text, MESH, label=label, engine="rows")
        assert tr.by_kind_and_link() == ref.by_kind_and_link()
        assert tr.by_semantic() == ref.by_semantic()


def test_session_ingest_cli(tmp_path, capsys):
    from repro.core.session import _main
    paths = []
    for i in range(2):
        p = tmp_path / f"run{i}.hlo"
        p.write_text(synthetic_hlo(n_sites=50, seed=i))
        paths.append(str(p))
    out = str(tmp_path / "sweep.json")
    assert _main(["ingest", out, *paths, "--mesh", "2,4",
                  "--axes", "data,model", "--workers", "1"]) == 0
    captured = capsys.readouterr().out
    assert "ingested 2 traces" in captured
    from repro.core.session import TraceSession
    assert TraceSession.load(out).labels() == ["run0", "run1"]
