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


def _three_routes(files, conjuncts, metadata=None, state=None):
    """(arrow, twin, device) keep-masks for one corpus entry, the twin
    and the kernel over `state`'s resident index (a fresh one when no
    state is given)."""
    arrow = skipping_mask(files, conjuncts, metadata)
    st = state if state is not None else _FakeState(files)
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


def test_index_lifecycle_end_to_end(tmp_table_path, monkeypatch):
    from delta_tpu.expressions import col as tcol, lit as tlit
    from delta_tpu.obs import hbm
    from delta_tpu.parallel.resident import release_snapshot_resident

    def on_chip():
        return [r for r in hbm.residents()
                if r["kind"] == hbm.KIND_STATS_INDEX
                and r["table_path"] == str(tmp_table_path)]

    monkeypatch.setenv("DELTA_TPU_DEVICE_SKIP", "force")
    builds = obs.counter("scan.stats_index_builds")
    appends = obs.counter("scan.stats_index_appends")
    dta.write_table(
        tmp_table_path,
        pa.table({"id": pa.array(np.arange(500, dtype=np.int64))}),
        target_rows_per_file=100,
    )
    snap = Table.for_path(tmp_table_path).latest_snapshot()
    b0, a0 = builds.value, appends.value
    flt = (tcol("id") >= tlit(0)) & (tcol("id") < tlit(100))
    assert snap.scan(filter=flt).add_files_table().num_rows == 1
    assert snap.scan(filter=flt).add_files_table().num_rows == 1
    # two scans of one version: ONE build, the second plan reuses it
    assert builds.value == b0 + 1
    old_index = snap.state.stats_index
    assert old_index is not None
    assert [r["version"] for r in on_chip()] == [snap.version]

    # update() with a real delta produces a fresh state; the old
    # version's index is released by advance_state, device copy and
    # ledger entry at once, and what the next scan needs of it goes on
    # as a seed
    dta.write_table(
        tmp_table_path,
        pa.table({"id": pa.array(np.arange(500, 600, dtype=np.int64))}))
    snap2 = snap.update()
    assert snap2.state.stats_index is None
    assert snap.state.stats_index is None  # released, not leaked
    assert old_index.released and old_index.vals is None
    assert on_chip() == []
    assert snap2.state.stats_index_seed is not None
    assert snap.state.stats_index_seed is None

    # the next scan brings the index to the new version from the seed:
    # no build from nothing, one append, the seed consumed
    assert snap2.scan(filter=flt).add_files_table().num_rows == 1
    assert builds.value == b0 + 1
    assert appends.value == a0 + 1
    assert snap2.state.stats_index_seed is None
    assert snap2.state.stats_index.version == snap2.version
    assert [r["version"] for r in on_chip()] == [snap2.version]

    # a reader still holding the old snapshot plans on it correctly,
    # by a build from nothing (its index went with the advance)
    late = (tcol("id") >= tlit(500)) & (tcol("id") < tlit(600))
    assert snap.scan(filter=late).add_files_table().num_rows == 0
    assert snap2.scan(filter=late).add_files_table().num_rows == 1
    assert snap.scan(filter=flt).add_files_table().num_rows == 1
    assert builds.value == b0 + 2
    assert appends.value == a0 + 1

    # eviction discipline: release_snapshot_resident frees the index,
    # and a seed no scan has consumed
    release_snapshot_resident(snap2)
    assert snap2.state.stats_index is None
    dta.write_table(
        tmp_table_path,
        pa.table({"id": pa.array(np.arange(600, 700, dtype=np.int64))}))
    snap3 = snap.update()
    assert snap3.state.stats_index_seed is not None
    release_snapshot_resident(snap3)
    assert snap3.state.stats_index_seed is None
    release_snapshot_resident(snap)
    assert on_chip() == []


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


def _host_form(lanes):
    """(int64 values, validity) `[R, n_pad]` of a device copy (high,
    low, validity), each `[R, n_pad / 128, 128]`."""
    high, low, valid = (np.asarray(a) for a in lanes)
    assert (high.dtype, low.dtype, valid.dtype) == (np.int32, np.uint32, bool)
    assert high.shape == low.shape == valid.shape and high.shape[2] == 128
    flat = (high.shape[0], -1)
    vals = (high.astype(np.int64) << 32) | low.astype(np.int64)
    return vals.reshape(flat), valid.reshape(flat)


@pytest.mark.parametrize("lanes,n_pad", [
    (4, 128), (4, 4096), (4, 1 << 20), (4, (1 << 20) + (1 << 19)),
    # more rows than cross at a time: two pieces, and nine
    (13, 4096), (70, 1024)])
def test_uploaded_index_is_bit_identical(lanes, n_pad):
    """The lanes cross the link as int64 and are split on the device
    into their 32-bit halves; the validity plane crosses as packed
    32-bit words and is unpacked there by shift-and-mask: every value
    and every flag of every lane comes back where the host had it."""
    from delta_tpu.stats.device_index import ResidentStatsIndex

    rng = np.random.default_rng(n_pad + lanes)
    valid = rng.random((lanes, n_pad)) < 0.5
    valid[0, :3] = [True, False, True]
    valid[-1, -1] = True
    vals = rng.integers(-2**63, 2**63 - 1, (lanes, n_pad), endpoint=True)
    vals[:, :4] = [-2**63, -1, 2**31, 2**63 - 1]
    idx = ResidentStatsIndex(None, vals, valid, {}, n_pad - 5)
    try:
        dvals, dvalid = _host_form(idx.device_lanes())
        assert dvals.shape == (lanes, n_pad)
        assert np.array_equal(dvalid, valid)
        assert np.array_equal(dvals, vals)
    finally:
        idx.release()


# ---- the kernel over halves decides every comparison as int64 does ----

_I64 = np.iinfo(np.int64)
# the ends of int64, of its halves, and values whose high halves are
# equal and whose low halves lie either side of 2^31 (where a signed
# comparison of the low half would turn)
_EDGES = [_I64.min, -2**32 - 1, -1, 0, 2**31 - 1, 2**31, 2**32 - 1, 2**32,
          _I64.max, (5 << 32) + 2**31 - 1, (5 << 32) + 2**31,
          (-7 << 32) + 2**31 - 1, (-7 << 32) + 2**31]
_STAT_ROWS = ("min", "max", "nullCount", "numRecords")


def _edge_index(invalid):
    """One column's lanes over every (min, max) pair of `_EDGES`, each
    with nullCount none, some and all of numRecords (an edge itself on
    every third file, so `nc == nr` is decided at the edges too);
    `invalid` names the stat row whose flags are off on every other
    file."""
    from delta_tpu.stats.device_index import ResidentStatsIndex

    pairs = [(a, b) for a in _EDGES for b in _EDGES]
    n = 3 * len(pairs)
    n_pad = 512
    assert n <= n_pad
    vals = np.zeros((4, n_pad), np.int64)
    valid = np.zeros((4, n_pad), bool)
    valid[:, :n] = True
    for k, (a, b) in enumerate(pairs):
        nr = _EDGES[k % len(_EDGES)] if k % 3 == 0 else 10
        vals[0, 3 * k: 3 * k + 3] = a
        vals[1, 3 * k: 3 * k + 3] = b
        vals[2, 3 * k: 3 * k + 3] = [0, 3, nr]
        vals[3, 3 * k: 3 * k + 3] = nr
    if invalid is not None:
        valid[_STAT_ROWS.index(invalid), :n:2] = False
    return ResidentStatsIndex(None, vals, valid, {}, n), n


def _atoms(ops, lits, sizes):
    """An `AtomBlock` on column 0: `ops[i]` against `lits[i]`, grouped
    by `sizes`."""
    from delta_tpu.ops.skipping import AtomBlock

    n = len(ops)
    assert sum(sizes) == n
    rows = np.zeros(n, np.int32)
    return AtomBlock(
        rows_mn=rows, rows_mx=rows + 1, rows_nc=rows + 2,
        ops=np.asarray(ops, np.int32), lits=np.asarray(lits, np.int64),
        grp=np.repeat(np.arange(len(sizes)), sizes).astype(np.int32),
        n_atoms=n, n_groups=len(sizes))


def _edge_cases():
    # every op alone against every edge (one atom of two slots), with
    # each stat row's validity off in turn
    for op in range(8):
        for invalid in (None,) + _STAT_ROWS:
            yield pytest.param(("op", op, invalid),
                               id=f"op{op}-{invalid or 'valid'}")
    # atoms that fill their slots, so that a group closes on the last
    # one; and fewer atoms than slots
    for sizes in ([1, 1], [2], [3, 1], [1, 3], [2, 2, 2, 2], [1, 6, 1],
                  [8], [1], [2, 1], [1, 2, 2], [4, 3, 6], [13]):
        yield pytest.param(("groups", sizes, None),
                           id="groups-" + "-".join(map(str, sizes)))


@pytest.mark.parametrize("case", _edge_cases())
def test_kernel_over_halves_equals_the_twin_at_the_edges(case):
    from delta_tpu.ops import skipping as ops_skipping

    kind, what, invalid = case
    idx, n = _edge_index(invalid)
    if kind == "op":
        blocks = [_atoms([what], [lit], [1]) for lit in _EDGES]
    else:
        rng = np.random.default_rng(sum(what) * 31 + len(what))
        k = sum(what)
        blocks = [_atoms(rng.integers(0, 8, k), rng.choice(_EDGES, k), what)
                  for _ in range(6)]
    try:
        lanes = idx.device_lanes()
        kept = set()
        for block in blocks:
            twin = ops_skipping.host_skip_mask(idx.vals, idx.valid, block, n)
            device = ops_skipping.skip_mask_block(*lanes, block, n)
            assert device.dtype == np.bool_ and device.shape == (n,)
            assert np.array_equal(device, twin), (
                block.ops, block.lits, np.flatnonzero(device != twin)[:8])
            kept.add(int(twin.sum()))
        # the case decides something: not every launch keeps every file
        # or none (an op alone against thirteen literals, six drawn sets
        # of atoms)
        assert kept - {0, n}
    finally:
        idx.release()


@pytest.mark.parametrize("lanes", [4, 13])
def test_lanes_are_split_once_an_upload_never_a_launch(lanes):
    """`scan.stats_index_lane_splits`: +1 when the index crosses to the
    chip, +0 for each plan on it, +1 again when an evicted index is
    uploaded anew; the span says in which form the lanes are resident,
    and the kernel's program takes no int64."""
    from delta_tpu.ops import skipping as ops_skipping
    from delta_tpu.stats.device_index import ResidentStatsIndex

    splits = obs.counter("scan.stats_index_lane_splits")
    rng = np.random.default_rng(lanes)
    vals = rng.integers(-2**40, 2**40, (lanes, 256))
    idx = ResidentStatsIndex(None, vals, np.ones((lanes, 256), bool), {}, 250)
    block = _atoms([3, 0], [-5, 2**33], [1, 1])
    twin = ops_skipping.host_skip_mask(idx.vals, idx.valid, block, 250)
    obs.set_trace_mode("on")
    try:
        obs.reset_trace_buffer()
        before = splits.value
        first = idx.device_lanes()
        assert splits.value == before + 1
        for _ in range(3):
            assert idx.device_lanes()[0] is first[0]
            assert np.array_equal(
                ops_skipping.skip_mask_block(*first, block, 250), twin)
        assert splits.value == before + 1
        idx.evict_device()
        again = idx.device_lanes()
        assert again[0] is not first[0]
        assert splits.value == before + 2
        assert np.array_equal(
            ops_skipping.skip_mask_block(*again, block, 250), twin)
        uploads = [s for s in obs.get_finished_spans()
                   if s.name == "stats.index_upload"]
    finally:
        obs.set_trace_mode(None)
        idx.release()
    assert [s.attrs["form"] for s in uploads] == ["halves", "halves"]
    assert all(str(a.dtype) in ("int32", "uint32", "bool") for a in again)


# ---- the index brought to a new version from the one before ----

_PROTOCOL = {"protocol": {"minReaderVersion": 1, "minWriterVersion": 2}}
_METADATA = {"metaData": {
    "id": "appended-index", "format": {"provider": "parquet", "options": {}},
    "schemaString": json.dumps({"type": "struct", "fields": [
        {"name": n, "type": t, "nullable": True, "metadata": {}}
        for n, t in (("a", "long"), ("f", "double"), ("s", "string"))]}),
    "partitionColumns": [], "configuration": {}}}


def _file_stats(fid):
    """Stats of file `fid`: an int, a float and a string column."""
    return _stats(10, {"a": fid * 10, "f": fid * 1.5 - 40.25, "s": f"k{fid:05d}"},
                  {"a": fid * 10 + 9, "f": fid * 1.5 - 39.5, "s": f"k{fid:05d}z"},
                  {"a": fid % 3, "f": 0, "s": 0})


class _Log:
    """A table written commit by commit as raw JSON, so that a test
    chooses every stats string and every remove."""

    def __init__(self, root, n_base):
        self.root = str(root)
        self.log = os.path.join(self.root, "_delta_log")
        os.makedirs(self.log)
        self.version = -1
        self.next_fid = 0
        self.commit(extra=[_PROTOCOL, _METADATA])
        self.commit(adds=self.new_files(n_base))

    def new_files(self, k, stats=_file_stats):
        fids = range(self.next_fid, self.next_fid + k)
        self.next_fid += k
        return [(f"part-{fid:05d}.parquet", stats(fid)) for fid in fids]

    def commit(self, adds=(), removes=(), extra=()):
        self.version += 1
        actions = list(extra)
        actions += [{"remove": {"path": p, "deletionTimestamp": self.version,
                                "dataChange": True}} for p in removes]
        actions += [{"add": {"path": p, "partitionValues": {}, "size": 100,
                             "modificationTime": self.version,
                             "dataChange": True, "stats": st}}
                    for p, st in adds]
        with open(os.path.join(self.log, f"{self.version:020d}.json"),
                  "w") as f:
            f.writelines(json.dumps(a) + "\n" for a in actions)


_CONJUNCTS = [
    [Comparison("<", col("a"), lit(300))],
    [Comparison(">=", col("f"), lit(100.0)), Comparison("<", col("a"), lit(1200))],
    [Comparison(">=", col("a"), lit(1250))],
    # a string column goes down the Arrow ladder, over the parsed table
    [Comparison(">=", col("s"), lit("k00125")), Comparison(">", col("a"), lit(40))],
    [IsNull(col("a"))],
    [Or(Comparison("=", col("a"), lit(55)), Comparison(">", col("f"), lit(150.0)))],
]


def _live_paths(snapshot):
    return snapshot.state.add_files_table.column("path").to_pylist()


_INDEX_COUNTERS = [obs.counter("scan.stats_index_" + name)
                   for name in ("builds", "appends", "append_fallbacks")]


def _assert_index_as_built_from_nothing(snapshot):
    """The state's resident index, and every route's keep-mask over it,
    equal what a build over every live file's stats string gives.
    Returns how the state came by its index: (builds from nothing,
    appends, appends that fell back), counted round that one call."""
    from delta_tpu.stats.device_index import build_index, snapshot_stats_index

    state = snapshot.state
    files = state.add_files_table
    before = [c.value for c in _INDEX_COUNTERS]
    idx = snapshot_stats_index(state, files)
    how = tuple(c.value - b for c, b in zip(_INDEX_COUNTERS, before))
    ref = build_index(files)
    n = ref.n
    assert idx.n == n == files.num_rows
    assert list(idx.cols.items()) == list(ref.cols.items())
    assert idx.vals.shape == ref.vals.shape == idx.valid.shape
    assert np.array_equal(idx.vals, ref.vals)           # the padding too
    assert np.array_equal(idx.valid, ref.valid)
    assert not idx.vals[:, n:].any() and not idx.valid[:, n:].any()
    assert idx.arrow_index.n == n
    assert idx.arrow_index._table.equals(ref.arrow_index._table)
    assert idx.arrow_index._table.schema.equals(ref.arrow_index._table.schema)
    for conjs in _CONJUNCTS:
        arrow, twin, device = _three_routes(files, conjs, state=state)
        assert (arrow == twin).all() and (twin == device).all(), conjs
        fresh = _three_routes(files, conjs)[1]
        assert (twin == fresh).all(), conjs
    return how


def _null_some(fid):
    return None if fid % 3 == 0 else _file_stats(fid)


def _adds(log, rng, live):
    """Three refreshes, each of one commit that only adds."""
    for _ in range(3):
        log.commit(adds=log.new_files(int(rng.integers(1, 9))))
        yield "scan"


def _removes_of_checkpoint_files(log, rng, live):
    for _ in range(3):
        gone = rng.choice(live()[:100], int(rng.integers(1, 6)), replace=False)
        log.commit(adds=log.new_files(int(rng.integers(0, 5))),
                   removes=list(gone))
        yield "scan"


def _removes_of_delta_files(log, rng, live):
    """Files of an earlier delta go: one the index already holds, and
    one that landed and went between two scans."""
    log.commit(adds=log.new_files(6))
    yield "scan"
    log.commit(adds=log.new_files(4), removes=live()[-3:-1])
    yield "scan"
    log.commit(adds=log.new_files(5))
    yield "update"
    log.commit(removes=[live()[-2], live()[3]])
    yield "scan"
    log.commit(removes=live()[-4:])             # a delta of removes alone
    yield "scan"


def _removed_and_added_again(log, rng, live):
    back = live()[7]
    log.commit(removes=[back])
    yield "scan"
    log.commit(adds=[(back, _file_stats(700))])
    yield "scan"
    again = live()[11]
    log.commit(removes=[again])
    log.commit(adds=[(again, _file_stats(701))] + log.new_files(2))
    yield "scan"                                # both in one refresh
    log.commit(adds=[(again, _file_stats(702))])    # re-added while live
    yield "scan"


def _rows_without_stats(log, rng, live):
    for _ in range(3):
        log.commit(adds=log.new_files(7, stats=_null_some),
                   removes=[live()[int(rng.integers(0, 100))]])
        yield "scan"


def _several_updates_between_scans(log, rng, live):
    for _ in range(2):
        for _ in range(3):
            log.commit(adds=log.new_files(int(rng.integers(1, 6))),
                       removes=[live()[int(rng.integers(0, 110))]])
            yield "update"
        yield "scan"


def _empty_delta_after_a_landed_one(log, rng, live):
    log.commit(adds=log.new_files(3), removes=live()[:2])
    yield "update"
    log.commit(extra=[{"txn": {"appId": "writer", "version": 1}}])
    yield "scan"                                # the seed came through
    log.commit(adds=log.new_files(2))
    yield "update"
    log.commit(extra=[{"txn": {"appId": "writer", "version": 2}}])
    yield "update"
    log.commit(extra=[{"txn": {"appId": "writer", "version": 3}}])
    yield "scan"


def _across_a_pad_bucket(log, rng, live):
    from delta_tpu.stats.device_index import snapshot_stats_index

    def n_pad(snapshot):
        state = snapshot.state
        return snapshot_stats_index(
            state, state.add_files_table).vals.shape[1]

    assert n_pad((yield "snapshot")) == 128
    log.commit(adds=log.new_files(5), removes=live()[:1])
    assert n_pad((yield "scan")) == 128         # 124 rows
    log.commit(adds=log.new_files(20), removes=live()[:3])
    assert n_pad((yield "scan")) == 256         # 141 rows
    log.commit(removes=live()[:30])
    assert n_pad((yield "scan")) == 128         # and back: 111 rows


@pytest.mark.parametrize("start", ["json", "checkpoint", "resident"])
@pytest.mark.parametrize("sequence", [
    _adds, _removes_of_checkpoint_files, _removes_of_delta_files,
    _removed_and_added_again, _rows_without_stats,
    _several_updates_between_scans, _empty_delta_after_a_landed_one,
    _across_a_pad_bucket], ids=lambda f: f.__name__.strip("_"))
def test_appended_index_equals_one_built_from_nothing(
        tmp_path, sequence, start):
    """Over seeded random sequences of commits, the index that each
    refresh makes from the version before equals `build_index` over
    every live file, and plans the same files on all three routes:
    on a state loaded from commits, from a checkpoint, and one whose
    replay state is resident on the device (`update.advance` then takes
    the masks of old rows and new from the device)."""
    from delta_tpu.engine.tpu import TpuEngine

    engine = TpuEngine(replay_shards=8) if start == "resident" else None
    for seed in range(3):
        rng = np.random.default_rng([seed, len(sequence.__name__)])
        log = _Log(tmp_path / f"t{seed}", n_base=120)
        table = Table.for_path(log.root, engine)
        if start == "checkpoint":
            table.checkpoint()
            table = Table.for_path(log.root)
        holder = [table.latest_snapshot()]
        assert _assert_index_as_built_from_nothing(holder[0]) == (1, 0, 0)
        assert (holder[0].state.resident is not None) == (start == "resident")
        scans = 0
        steps = sequence(log, rng, lambda: _live_paths(holder[0]))
        step = next(steps)
        while True:
            if step != "snapshot":
                holder[0] = holder[0].update()
                assert holder[0].version == log.version
            if step == "scan":
                # an append, not a build from every stats string, and
                # not one that fell back
                assert _assert_index_as_built_from_nothing(
                    holder[0]) == (0, 1, 0)
                scans += 1
            try:
                step = steps.send(holder[0])
            except StopIteration:
                break
        assert scans >= 2
        # and the table as a process that never saw the versions between
        fresh = Table.for_path(log.root).latest_snapshot()
        assert _live_paths(fresh) == _live_paths(holder[0])


def _float_in_an_int_column(fid):
    return _file_stats(fid).replace(f'"a": {fid * 10},', f'"a": {fid * 10}.5,')


def _a_new_column(fid):
    st = json.loads(_file_stats(fid))
    for group in ("minValues", "maxValues", "nullCount"):
        st[group]["b"] = fid
    return json.dumps(st)


def _a_nan_token(fid):
    if fid % 2:
        return _file_stats(fid)
    return _stats(10, {"a": fid * 10, "f": "NaN", "s": "k"},
                  {"a": fid * 10 + 9, "f": "NaN", "s": "kz"},
                  {"a": 0, "f": 0, "s": 0})


def _counts_alone(fid):
    return _stats(10)


@pytest.mark.parametrize("base_stats,delta_stats,reason", [
    (_file_stats, _float_in_an_int_column, "leaf-type"),
    (_file_stats, _a_new_column, "new-leaf"),
    (_file_stats, _a_nan_token, "tail-unparsed"),
    (_file_stats, lambda fid: None, "tail-without-stats"),
    (_counts_alone, _file_stats, "seed-without-lanes"),
], ids=["int-to-float", "new-column", "nan-token", "no-stats",
        "seed-without-lanes"])
def test_append_falls_back_to_a_full_build(tmp_path, base_stats,
                                           delta_stats, reason):
    """Where the delta's stats do not read under the seed's schema, or
    the seed has no lanes, the refresh builds from every live file,
    says why, and plans what a fresh load of the table plans."""
    log = _Log(tmp_path / "t", n_base=0)
    log.commit(adds=log.new_files(40, stats=base_stats))
    snap = Table.for_path(log.root).latest_snapshot()
    conjs = _CONJUNCTS[1]
    skipping_mask(snap.state.add_files_table, conjs, None, state=snap.state)
    log.commit(adds=log.new_files(6, stats=delta_stats),
               removes=_live_paths(snap)[:2])
    snap = snap.update()
    assert snap.state.stats_index_seed is not None
    obs.set_trace_mode("on")
    try:
        obs.reset_trace_buffer()
        assert _assert_index_as_built_from_nothing(snap) == (1, 0, 1)
        build = [s.to_dict()["attrs"] for s in obs.get_finished_spans()
                 if s.name == "stats.index_build"][0]
    finally:
        obs.set_trace_mode(None)
        obs.reset_trace_buffer()
    assert build["mode"] == "full" and build["append_fallback"] == reason
    assert build["rows"] == 44
    assert snap.state.stats_index_seed is None
    fresh = Table.for_path(log.root).latest_snapshot()
    files, fresh_files = snap.state.add_files_table, fresh.state.add_files_table
    for conjs in _CONJUNCTS:
        keep = skipping_mask(files, conjs, None, state=snap.state)
        fresh_keep = skipping_mask(fresh_files, conjs, None,
                                   state=fresh.state)
        assert files.filter(pa.array(keep)).column("path").to_pylist() == \
            fresh_files.filter(pa.array(fresh_keep)).column(
                "path").to_pylist()
    # the full build's index seeds the next refresh like any other
    log.commit(adds=log.new_files(3, stats=delta_stats))
    snap = snap.update()
    _assert_index_as_built_from_nothing(snap)


def test_build_span_says_append_and_counts_rows(tmp_path):
    log = _Log(tmp_path / "t", n_base=50)
    snap = Table.for_path(log.root).latest_snapshot()
    _assert_index_as_built_from_nothing(snap)
    obs.set_trace_mode("on")
    try:
        obs.reset_trace_buffer()
        log.commit(adds=log.new_files(8), removes=_live_paths(snap)[:3])
        snap = snap.update()
        log.commit(adds=log.new_files(2), removes=_live_paths(snap)[-1:])
        snap = snap.update()
        assert _assert_index_as_built_from_nothing(snap) == (0, 1, 0)
        spans = [(s.name, s.to_dict()["attrs"])
                 for s in obs.get_finished_spans()]
    finally:
        obs.set_trace_mode(None)
        obs.reset_trace_buffer()
    assert [a["stats_index_seed"] for name, a in spans
            if name == "update.advance"] == ["kept", "passed_on"]
    build = [a for name, a in spans if name == "stats.index_build"][0]
    assert build["mode"] == "append" and "append_fallback" not in build
    assert build["rows"] == 9 and build["dropped"] == 3 and build["lanes"] == 7
    assert build["bytes"] > 0


def test_scans_race_refreshes_and_every_plan_is_its_versions(tmp_path):
    """Readers plan on whatever snapshot is newest, and on the one
    before, while a writer lands commits and refreshes: the seed and
    the index change hands under `_stats_index_lock`, and every plan
    is the plan of its own version."""
    import sys

    log = _Log(tmp_path / "t", n_base=60)
    table = Table.for_path(log.root)
    newest = [table.latest_snapshot()]
    live_at = {newest[0].version: _live_paths(newest[0])}
    conjs = [Comparison("<", col("a"), lit(300))]
    errors, plans, done = [], [0], threading.Event()

    def wanted(version):        # file `fid` has a in [10 fid, 10 fid + 9]
        return [p for p in live_at[version] if int(p[5:10]) * 10 < 300]

    def reader():
        try:
            prior = snap = newest[0]
            while not done.is_set() or plans[0] < 20:
                prior, snap = snap, newest[0]
                for s in (snap, prior):
                    files = s.state.add_files_table
                    keep = skipping_mask(files, conjs, None, state=s.state)
                    got = files.filter(pa.array(keep)).column("path")
                    assert got.to_pylist() == wanted(s.version), s.version
                    plans[0] += 1
        except Exception as e:          # raised by the main thread
            errors.append(e)

    readers = [threading.Thread(target=reader) for _ in range(3)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in readers:
            t.start()
        for k in range(12):
            live = live_at[newest[0].version]
            log.commit(adds=log.new_files(3), removes=[live[k], live[-1]])
            snap = newest[0].update()
            live_at[snap.version] = _live_paths(snap)
            newest[0] = snap
        done.set()
        for t in readers:
            t.join(timeout=60)
    finally:
        done.set()
        sys.setswitchinterval(interval)
    if errors:
        raise errors[0]
    assert not any(t.is_alive() for t in readers)
    assert plans[0] >= 20
    _assert_index_as_built_from_nothing(newest[0])


# -- the in-place kernel: every atom count, grouping and op code --------------

_SLOT_FILES = 331     # no bucket: the index pads it to 512


@pytest.fixture(scope="module")
def slot_files():
    """331 files over an int, a float and a nullable int column: some
    with no stats at all, some with one side of a column missing, some
    where `n` is all null (nullCount == numRecords)."""
    rng = np.random.default_rng(30)
    rows = []
    for i in range(_SLOT_FILES):
        if i % 23 == 5:
            rows.append(None)
            continue
        num = int(rng.integers(1, 40))
        lo = int(rng.integers(0, 1000))
        flo = float(rng.normal(scale=100.0))
        mn = {"a": lo, "f": flo, "n": lo // 2}
        mx = {"a": lo + int(rng.integers(0, 200)), "f": flo + 25.0,
              "n": lo // 2 + 10}
        nc = {"a": 0, "f": int(rng.integers(0, 2)),
              "n": int(rng.integers(0, num))}
        if i % 7 == 3:
            nc["n"] = num                       # all null
            del mn["n"], mx["n"]
        if i % 11 == 4:
            del mn["f"]                         # one-sided
        if i % 13 == 6:
            del mx["a"], nc["a"]
        rows.append(_stats(num, mn, mx, nc))
    return _files(rows)


def _slot_atom(i, rng):
    """Atom `i` of a case: op code `i % 8`, columns in turn, a literal
    inside the data's range so that no atom decides every file."""
    name = ("a", "f", "n")[i % 3]
    column = col(name)
    code = i % 8
    if code == 6:
        return IsNull(column)
    if code == 7:
        return IsNotNull(column)
    value = float(rng.normal(scale=100.0)) if name == "f" \
        else int(rng.integers(0, 1100))
    return Comparison(("<", "<=", ">", ">=", "=", "!=")[code], column,
                      lit(value))


def _slot_conjuncts(n_atoms, grouping, rng):
    atoms = [_slot_atom(i, rng) for i in range(n_atoms)]
    if grouping == "and":                       # groups of one atom
        sizes = [1] * n_atoms
    elif grouping == "or":                      # one group of them all
        sizes = [n_atoms]
    else:                                       # 1, 2, 3, 1, 2, 3, ...
        sizes, k = [], 0
        while sum(sizes) < n_atoms:
            sizes.append(min(k % 3 + 1, n_atoms - sum(sizes)))
            k += 1
    conjuncts, at = [], 0
    for size in sizes:
        group = atoms[at]
        for other in atoms[at + 1: at + size]:
            group = Or(group, other)
        conjuncts.append(group)
        at += size
    return conjuncts, len(sizes)


@pytest.mark.parametrize("grouping", ["and", "or", "mixed"])
@pytest.mark.parametrize("n_atoms", [1, 2, 3, 5, 16, 17, 40])
def test_in_place_kernel_equals_the_twin_and_the_ladder(
        slot_files, n_atoms, grouping):
    from delta_tpu.ops import skipping as ops_skipping
    from delta_tpu.stats.device_index import (
        compile_conjuncts,
        snapshot_stats_index,
    )

    files = slot_files
    rng = np.random.default_rng(1000 * n_atoms + len(grouping))
    conjuncts, n_groups = _slot_conjuncts(n_atoms, grouping, rng)
    state = _FakeState(files)
    rs = snapshot_stats_index(state, files)
    block, fallback = compile_conjuncts(conjuncts, rs)
    # the case is what its name says: every atom in the one dispatch
    assert (block.n_atoms, block.n_groups, fallback) == (
        n_atoms, n_groups, [])
    assert rs.vals.shape[1] == 512

    twin = ops_skipping.host_skip_mask(rs.vals, rs.valid, block,
                                       _SLOT_FILES)
    lanes = rs.device_lanes()
    obs.set_trace_mode("on")
    obs.set_device_obs_mode("on")
    try:
        obs.reset_trace_buffer()
        obs.reset_device_obs()
        device = ops_skipping.skip_mask_block(*lanes, block, _SLOT_FILES)
        [wait] = [s for s in obs.get_finished_spans()
                  if s.name == "skip.wait"]
    finally:
        obs.set_trace_mode(None)
        obs.set_device_obs_mode(None)
    assert device.dtype == np.bool_ and device.shape == (_SLOT_FILES,)
    assert (device == twin).all()
    [rec] = [r for r in obs.get_dispatch_records()
             if r["kernel"] == "skipping.mask_block"]
    a_pad = max(2, 1 << (n_atoms - 1).bit_length())
    assert rec["key"] == repr(a_pad)
    assert rec["attrs"] == {"lanes": rs.vals.shape[0], "n_pad": 512}
    assert (wait.attrs["atoms"], wait.attrs["a_pad"]) == (n_atoms, a_pad)

    arrow, twin_routed, device_routed = _three_routes(
        files, conjuncts, state=state)
    assert (arrow == twin).all()
    assert (twin_routed == twin).all() and (device_routed == twin).all()
    # forty ANDed atoms keep next to nothing and forty ORed everything;
    # the mixed and the small cases keep some files and skip others
    if grouping == "mixed" or n_atoms <= 3:
        assert 0 < int(twin.sum()) < _SLOT_FILES


def test_literals_and_columns_are_arguments_not_compile_keys(slot_files):
    """Every plan of a cell has another `lo`/`hi`: a compilation for
    each would void its runs."""
    from delta_tpu.ops import skipping as ops_skipping

    files = slot_files
    state = _FakeState(files)
    ops_skipping._skip_fn_cached.cache_clear()
    for lo, name in ((100, "a"), (400, "n"), (-3.5, "f")):
        conjs = [Comparison(">=", col(name), lit(lo)),
                 Comparison("<", col(name), lit(lo + 90))]
        arrow, twin, device = _three_routes(files, conjs, state=state)
        assert (arrow == twin).all() and (twin == device).all()
    assert ops_skipping._skip_fn_cached.cache_info().currsize == 1
    assert ops_skipping._skip_fn_cached(2)._cache_size() == 1


@pytest.mark.parametrize("a_pad", [2, 16])
def test_kernel_temporaries_do_not_grow_with_the_atom_slots(a_pad):
    """At the size of `ckpt-query-under-ingest`'s index (4 lanes over
    2,621,440 padded files) the compiled program keeps at most 16 bytes
    of temporaries a file. The program that gathered `[a_pad, n_pad]`
    copies of the lanes kept 132 (346 MB) on this backend and took
    15.1 ms a launch on the chip where its bytes move in 0.12."""
    import jax
    import jax.numpy as jnp

    from delta_tpu.ops import skipping as ops_skipping

    tiles = (4, 2_621_440 // 128, 128)
    atoms = jax.ShapeDtypeStruct((a_pad,), jnp.int32)
    compiled = ops_skipping._skip_fn_cached(a_pad).lower(
        jax.ShapeDtypeStruct(tiles, jnp.int32),
        jax.ShapeDtypeStruct(tiles, jnp.uint32),
        jax.ShapeDtypeStruct(tiles, jnp.bool_),
        atoms, atoms, atoms, atoms, atoms,
        jax.ShapeDtypeStruct((a_pad,), jnp.uint32), atoms,
        jax.ShapeDtypeStruct((), jnp.int32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes <= 16 * 2_621_440
