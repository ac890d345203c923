"""Host/device data-skipping parity and the resident stats index.

The batched skipping path (stats/device_index.py + ops/skipping.py)
must produce the SAME keep-mask as the per-conjunct Arrow ladder it
replaces, on every stats shape a real log can contain: missing stats,
all-null columns, NaN, negative/large int64, column-mapping physical
names, mixed eligible/ineligible columns. The device kernel and its
numpy twin are bit-identical by construction (same int64 formulas),
so parity is asserted three ways per corpus entry: Arrow (stateless)
== twin (state, DELTA_TPU_DEVICE_SKIP=off) == kernel (=force)."""

import json
import os
import threading

import numpy as np
import pyarrow as pa
import pytest

import delta_tpu.api as dta
from delta_tpu import obs
from delta_tpu.expressions.tree import (
    Comparison,
    In,
    IsNotNull,
    IsNull,
    Not,
    Or,
    col,
    lit,
)
from delta_tpu.stats.skipping import skipping_mask
from delta_tpu.table import Table


class _FakeState:
    """Duck-typed SnapshotState: just the fields snapshot_stats_index
    needs (plain attribute `add_files_table` keeps identity stable)."""

    def __init__(self, files):
        self.add_files_table = files
        self.stats_index = None
        self._stats_index_lock = threading.Lock()


def _files(stats_rows):
    return pa.table({
        "path": [f"f{i}.parquet" for i in range(len(stats_rows))],
        "stats": pa.array(stats_rows, pa.string()),
    })


def _three_routes(files, conjuncts, metadata=None):
    """(arrow, twin, device) keep-masks for one corpus entry."""
    arrow = skipping_mask(files, conjuncts, metadata)
    st = _FakeState(files)
    old = os.environ.get("DELTA_TPU_DEVICE_SKIP")
    try:
        os.environ["DELTA_TPU_DEVICE_SKIP"] = "off"
        twin = skipping_mask(files, conjuncts, metadata, state=st)
        os.environ["DELTA_TPU_DEVICE_SKIP"] = "force"
        device = skipping_mask(files, conjuncts, metadata, state=st)
    finally:
        if old is None:
            os.environ.pop("DELTA_TPU_DEVICE_SKIP", None)
        else:
            os.environ["DELTA_TPU_DEVICE_SKIP"] = old
    return arrow, twin, device


def _stats(num=10, mn=None, mx=None, nc=None):
    out = {"numRecords": num}
    if mn is not None:
        out["minValues"] = mn
    if mx is not None:
        out["maxValues"] = mx
    if nc is not None:
        out["nullCount"] = nc
    return json.dumps(out)


def test_basic_parity_int_float_bool():
    files = _files([
        _stats(10, {"a": 1, "f": -2.5, "b": False}, {"a": 9, "f": 3.5, "b": True}, {"a": 0, "f": 0, "b": 0}),
        _stats(10, {"a": 20, "f": 100.0, "b": True}, {"a": 30, "f": 200.0, "b": True}, {"a": 1, "f": 2, "b": 0}),
        None,  # missing stats: always keep
        _stats(4, {"a": -5}, {"a": -1}, {"a": 4}),  # all-null a
    ])
    corpus = [
        [Comparison("<", col("a"), lit(5))],
        [Comparison(">=", col("f"), lit(50.0))],
        [Comparison("=", col("b"), lit(False))],
        [Comparison("!=", col("a"), lit(25))],
        [IsNull(col("a"))],
        [IsNotNull(col("a"))],
        [Or(Comparison("=", col("a"), lit(25)),
            Comparison("<", col("f"), lit(0.0)))],
        [Not(Comparison(">", col("a"), lit(5)))],
        [Comparison("<", col("a"), lit(5)),
         Comparison(">", col("f"), lit(0.0))],
        # literal on the left (flip path)
        [Comparison(">", lit(5), col("a"))],
    ]
    for conjs in corpus:
        arrow, twin, device = _three_routes(files, conjs)
        assert (arrow == twin).all(), conjs
        assert (twin == device).all(), conjs


def test_randomized_property_corpus():
    rng = np.random.default_rng(7)
    ops = ["<", "<=", ">", ">=", "=", "!="]
    for trial in range(25):
        rows = []
        for _ in range(int(rng.integers(1, 12))):
            if rng.random() < 0.15:
                rows.append(None)  # no stats at all
                continue
            lo = int(rng.integers(-(2**62), 2**62))
            hi = lo + int(rng.integers(0, 2**10))
            num = int(rng.integers(1, 50))
            nc = int(rng.integers(0, num + 1))
            flo = float(rng.normal(scale=1e6))
            fhi = flo + abs(float(rng.normal(scale=10.0)))
            mn = {"big": lo, "f": flo, "s": "aaa"}
            mx = {"big": hi, "f": fhi, "s": "zzz"}
            if rng.random() < 0.2:
                del mn["f"], mx["f"]  # one-sided / missing column
            rows.append(_stats(num, mn, mx, {"big": nc, "f": 0, "s": 0}))
        files = _files(rows)
        conjs = []
        for _ in range(int(rng.integers(1, 4))):
            which = rng.random()
            if which < 0.4:
                conjs.append(Comparison(
                    str(rng.choice(ops)), col("big"),
                    lit(int(rng.integers(-(2**62), 2**62)))))
            elif which < 0.7:
                conjs.append(Comparison(
                    str(rng.choice(ops)), col("f"),
                    lit(float(rng.normal(scale=1e6)))))
            else:
                # ineligible (string) column: exercises the mixed
                # compiled + Arrow-fallback path
                conjs.append(Comparison("=", col("s"), lit("mmm")))
        arrow, twin, device = _three_routes(files, conjs)
        assert (twin == device).all(), (trial, conjs)
        assert (arrow == twin).all(), (trial, conjs)


def test_nan_and_inf_stats_keep_conservatively():
    # collection.py writes non-finite stats as JSON strings; whatever a
    # foreign writer produced, files with non-finite float stats must
    # never be wrongly skipped — and routes must agree
    files = _files([
        _stats(10, {"f": "NaN"}, {"f": "NaN"}, {"f": 0}),
        _stats(10, {"f": -1.0}, {"f": 1.0}, {"f": 0}),
        _stats(10, {"f": "-Infinity"}, {"f": "Infinity"}, {"f": 0}),
        _stats(10, {"f": 100.0}, {"f": 200.0}, {"f": 0}),
    ])
    for op in ["<", "<=", ">", ">=", "=", "!="]:
        arrow, twin, device = _three_routes(
            files, [Comparison(op, col("f"), lit(0.0))])
        assert (twin == device).all(), op
        # rows with non-finite stats are unknown -> kept, on every route
        assert arrow[0] and arrow[2], op
        # row 1 has clean numeric stats: every route must agree on it
        assert arrow[1] == twin[1], op
    # one NaN-stat file must NOT disable skipping for the whole table:
    # the clean out-of-range file still gets skipped
    arrow, twin, device = _three_routes(
        files, [Comparison("<", col("f"), lit(0.0))])
    assert arrow.tolist() == [True, True, True, False]
    assert (arrow == twin).all() and (twin == device).all()


def test_multiline_pretty_printed_stats_regression():
    # embedded newlines used to desync the one-row-per-line framing and
    # silently disable ALL skipping (parsed.num_rows != n -> keep all)
    pretty = json.dumps(
        {"numRecords": 10, "minValues": {"a": 1}, "maxValues": {"a": 5},
         "nullCount": {"a": 0}}, indent=2)
    assert "\n" in pretty
    compact = _stats(10, {"a": 100}, {"a": 200}, {"a": 0})
    files = _files([pretty, compact])
    conjs = [Comparison("<", col("a"), lit(50))]
    arrow, twin, device = _three_routes(files, conjs)
    # skipping WORKS: the second file is provably out of range
    assert arrow.tolist() == [True, False]
    assert (arrow == twin).all() and (twin == device).all()


def test_truncated_string_max_is_prefix_aware():
    from delta_tpu.stats.collection import MAX_STRING_PREFIX_LENGTH

    full = "m" * (MAX_STRING_PREFIX_LENGTH + 8)
    truncated = full[:MAX_STRING_PREFIX_LENGTH]  # plain prefix, no bump
    files = _files([
        _stats(10, {"s": "a"}, {"s": truncated}, {"s": 0}),
        _stats(10, {"s": "a"}, {"s": "k"}, {"s": 0}),  # exact short max
    ])
    # the true max may exceed the stored 32-char prefix: '>' against a
    # literal above the stored max must KEEP the truncated file...
    probe = truncated + "zzz"
    keep = skipping_mask(files, [Comparison(">", col("s"), lit(probe))], None)
    assert keep.tolist() == [True, False]
    # ...same for '>=' and '='
    keep = skipping_mask(files, [Comparison(">=", col("s"), lit(probe))], None)
    assert keep.tolist() == [True, False]
    keep = skipping_mask(files, [Comparison("=", col("s"), lit(probe))], None)
    assert keep.tolist() == [True, False]
    # '!=' may not prove "every row equals lit" from a truncated max
    eq_probe = truncated
    keep = skipping_mask(
        files, [Comparison("!=", col("s"), lit(eq_probe))], None)
    assert keep[0]
    # min-side comparisons need no guard and still skip below the min
    keep = skipping_mask(files, [Comparison("<", col("s"), lit("a"))], None)
    assert keep.tolist() == [False, False]


def test_in_list_prefilter_and_large_list():
    files = _files([
        _stats(10, {"a": 0}, {"a": 9}, {"a": 0}),
        _stats(10, {"a": 100}, {"a": 109}, {"a": 0}),
        _stats(10, {"a": 1000}, {"a": 1009}, {"a": 0}),
    ])
    small = In(col("a"), tuple(range(100, 105)))
    arrow, twin, device = _three_routes(files, [small])
    assert arrow.tolist() == [False, True, False]
    assert (arrow == twin).all() and (twin == device).all()
    # >64 values: the range prefilter is the whole verdict on every
    # route — conservative (a superset of the exact per-value OR) and
    # route-identical
    big = In(col("a"), tuple(range(100, 200)))
    arrow, twin, device = _three_routes(files, [big])
    assert not arrow[2] and arrow[1]
    assert (twin == device).all()
    # values straddling a gap: file 0 is outside [min, max] entirely
    assert not arrow[0]


def test_device_plan_counters_not_vacuous():
    plans = obs.counter("scan.device_plans")
    falls = obs.counter("scan.device_fallbacks")
    builds = obs.counter("scan.stats_index_builds")
    reuses = obs.counter("scan.stats_index_reuses")
    p0, f0, b0, r0 = plans.value, falls.value, builds.value, reuses.value
    files = _files([
        _stats(10, {"a": 1, "s": "a"}, {"a": 9, "s": "b"}, {"a": 0, "s": 0}),
    ])
    st = _FakeState(files)
    conjs = [Comparison("<", col("a"), lit(5)),
             Comparison("=", col("s"), lit("x"))]  # string -> fallback
    old = os.environ.get("DELTA_TPU_DEVICE_SKIP")
    try:
        os.environ["DELTA_TPU_DEVICE_SKIP"] = "force"
        skipping_mask(files, conjs, None, state=st)
        skipping_mask(files, conjs, None, state=st)
    finally:
        if old is None:
            os.environ.pop("DELTA_TPU_DEVICE_SKIP", None)
        else:
            os.environ["DELTA_TPU_DEVICE_SKIP"] = old
    assert plans.value == p0 + 2
    assert falls.value == f0 + 2  # one string conjunct per plan
    assert builds.value == b0 + 1  # built once...
    assert reuses.value == r0 + 1  # ...reused on the second plan


def test_column_mapping_physical_names_parity(tmp_table_path):
    dta.write_table(
        tmp_table_path,
        pa.table({"a": pa.array(np.arange(100, dtype=np.int64)),
                  "s": pa.array([f"v{i:03d}" for i in range(100)])}),
        properties={"delta.columnMapping.mode": "name"},
        target_rows_per_file=20,
    )
    snap = Table.for_path(tmp_table_path).latest_snapshot()
    files = snap.state.add_files_table
    conjs = [Comparison("<", col("a"), lit(20))]
    arrow = skipping_mask(files, conjs, snap.metadata)
    assert arrow.sum() == 1  # stats keyed by physical names still skip
    _, twin, device = _three_routes(files, conjs, snap.metadata)
    assert (arrow == twin).all() and (twin == device).all()


def test_index_lifecycle_end_to_end(tmp_table_path):
    from delta_tpu.expressions import col as tcol, lit as tlit
    from delta_tpu.parallel.resident import release_snapshot_resident

    builds = obs.counter("scan.stats_index_builds")
    dta.write_table(
        tmp_table_path,
        pa.table({"id": pa.array(np.arange(500, dtype=np.int64))}),
        target_rows_per_file=100,
    )
    snap = Table.for_path(tmp_table_path).latest_snapshot()
    b0 = builds.value
    flt = (tcol("id") >= tlit(0)) & (tcol("id") < tlit(100))
    assert snap.scan(filter=flt).add_files_table().num_rows == 1
    assert snap.scan(filter=flt).add_files_table().num_rows == 1
    # two scans of one version: ONE build, the second plan reuses it
    assert builds.value == b0 + 1
    assert snap.state.stats_index is not None

    # update() with a real delta produces a fresh state; the old
    # version's index was released by advance_state and the next scan
    # builds against the new version exactly once
    dta.write_table(
        tmp_table_path,
        pa.table({"id": pa.array(np.arange(500, 600, dtype=np.int64))}))
    snap2 = snap.update()
    assert snap2.state.stats_index is None
    assert snap.state.stats_index is None  # released, not leaked
    assert snap2.scan(filter=flt).add_files_table().num_rows == 1
    assert builds.value == b0 + 2

    # eviction discipline: release_snapshot_resident frees the index
    release_snapshot_resident(snap2)
    assert snap2.state.stats_index is None


def test_skip_route_gate():
    from delta_tpu.parallel.gate import skip_route

    old = os.environ.pop("DELTA_TPU_DEVICE_SKIP", None)
    try:
        # engine opt-in required before economics run
        assert skip_route(10_000, 8, engine_enabled=False) == "host"
        # tiny plans on an enabled engine: host still wins on CPU's
        # zero-RTT model only via the cell economics (both ~0) — the
        # env override is the deterministic way to force either route
        os.environ["DELTA_TPU_DEVICE_SKIP"] = "force"
        assert skip_route(1, 1) == "device"
        os.environ["DELTA_TPU_DEVICE_SKIP"] = "off"
        assert skip_route(1 << 30, 64, engine_enabled=True) == "host"
    finally:
        if old is None:
            os.environ.pop("DELTA_TPU_DEVICE_SKIP", None)
        else:
            os.environ["DELTA_TPU_DEVICE_SKIP"] = old


def test_partition_filter_does_not_disable_stats_skipping(tmp_table_path):
    # Expression.__eq__ builds a (truthy) Comparison node, so the old
    # `c not in part_conjuncts` classified EVERY conjunct as a
    # partition conjunct whenever one existed — data skipping silently
    # turned off on exactly the scans that combine both predicate kinds
    from delta_tpu.expressions import col as tcol, lit as tlit

    dta.write_table(
        tmp_table_path,
        pa.table({
            "p": pa.array([i // 50 for i in range(100)], pa.int64()),
            "v": pa.array(np.arange(100, dtype=np.int64)),
        }),
        partition_by=["p"],
        target_rows_per_file=10,
    )
    snap = Table.for_path(tmp_table_path).latest_snapshot()
    total = snap.state.add_files_table.num_rows
    sc = snap.scan(filter=(tcol("p") == tlit(0)) & (tcol("v") < tlit(10)))
    out = sc.add_files_table()
    assert sc.partition_pruned > 0  # partition p=1 files pruned
    assert sc.skipped_by_stats > 0  # v-range files within p=0 skipped
    assert out.num_rows == 1
    assert out.num_rows < total


def test_empty_delta_carries_index_forward(tmp_table_path):
    dta.write_table(
        tmp_table_path,
        pa.table({"id": pa.array(np.arange(100, dtype=np.int64))}))
    snap = Table.for_path(tmp_table_path).latest_snapshot()
    from delta_tpu.expressions import col as tcol, lit as tlit

    snap.scan(filter=tcol("id") < tlit(10)).add_files_table()
    idx = snap.state.stats_index
    assert idx is not None
    # no new commits: update() returns the same (or an equal) snapshot
    # and the index survives wherever the state landed
    snap2 = snap.update()
    holder = snap2.state.stats_index or snap.state.stats_index
    assert holder is idx


@pytest.mark.parametrize("n_pad", [128, 4096, 1 << 20, (1 << 20) + (1 << 19)])
def test_uploaded_validity_plane_is_bit_identical(n_pad):
    """The validity plane crosses the link as packed 32-bit words and is
    unpacked on the device by shift-and-mask: every flag of every lane
    comes back where the host had it."""
    from delta_tpu.stats.device_index import ResidentStatsIndex

    rng = np.random.default_rng(n_pad)
    valid = rng.random((4, n_pad)) < 0.5
    valid[0, :3] = [True, False, True]
    valid[3, -1] = True
    vals = rng.integers(-2**62, 2**62, (4, n_pad))
    idx = ResidentStatsIndex(None, vals, valid, {}, n_pad - 5)
    dvals, dvalid = idx.device_lanes()
    try:
        assert dvalid.dtype == bool and dvalid.shape == (4, n_pad)
        assert np.array_equal(np.asarray(dvalid), valid)
        assert np.array_equal(np.asarray(dvals), vals)
    finally:
        idx.release()
