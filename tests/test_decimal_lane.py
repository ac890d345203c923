"""The decimal lane and the AND under an OR (`stats/device_index.py`,
`stats/skipping.py`): a `decimal(p,s)` column of p <= 18 is on the
index's lanes as its unscaled value, read from the stat's digits and
never through a double; a literal meets it only where it is exact at the
column's scale; `(a1 AND a2) OR (b1 AND b2)` is distributed into the
kernel's OR-groups within the atom limit; and the kernel, its numpy twin
and the Arrow ladder give one mask."""

import decimal
import json

import numpy as np
import pyarrow as pa
import pytest

from delta_tpu import obs
from delta_tpu.expressions import col, lit
from delta_tpu.expressions.tree import split_conjuncts
from delta_tpu.models.actions import Metadata
from delta_tpu.models.schema import PrimitiveType, StructField, StructType
from delta_tpu.ops import skipping as ops_skipping
from delta_tpu.stats import skipping
from delta_tpu.stats.device_index import (IN_LIST_ATOM_LIMIT, append_index,
                                          build_index, compile_conjuncts,
                                          encode_literal)
from delta_tpu.stats.skipping import (StatsIndex, decimal_lane_type,
                                      decimal_literal, skipping_mask,
                                      stat_leaf_types)

D = decimal.Decimal
BIG = 1_234_567_890_123_400     # where a double is a quarter wide


def metadata_of(*fields):
    schema = StructType([StructField(name, PrimitiveType(kind))
                         for name, kind in fields])
    return Metadata(id="t", schemaString=json.dumps(schema.to_json_value()),
                    partitionColumns=[], configuration={})


MONEY = metadata_of(("q", "integer"), ("p", "decimal(7,2)"),
                    ("w", "decimal(18,2)"), ("z", "decimal(20,2)"))


def money_row(i, records=8):
    """File i: q in [i, i + 3]; p in [i.01, (i + 1).99]; w a cent a file
    from BIG.70, 16 digits before the point; z past 18 digits."""
    return ('{"numRecords":%d,"minValues":{"q":%d,"p":%d.01,"w":%d.7%d,'
            '"z":1.5},"maxValues":{"q":%d,"p":%d.99,"w":%d.7%d,"z":2.5},'
            '"nullCount":{"q":0,"p":0,"w":0,"z":0}}'
            % (records, i, i, BIG, i, i + 3, i + 1, BIG, i + 1))


def files_of(*rows):
    return pa.table({"stats": pa.array(list(rows), pa.string())})


FILES = files_of(*[money_row(i) for i in range(8)])


def masks(files, pred, md=MONEY):
    """The keep mask by the numpy twin, the kernel and the Arrow ladder
    alone; the three must be one."""
    idx = build_index(files, metadata=md)
    conjuncts = split_conjuncts(pred)
    block, fallback = compile_conjuncts(conjuncts, idx)
    n = files.num_rows
    out = {}
    if block is not None and not fallback:
        out["twin"] = ops_skipping.host_skip_mask(idx.vals, idx.valid, block,
                                                  n)
        out["kernel"] = ops_skipping.skip_mask_block(
            *idx.device_lanes(), block, n)
    ladder = np.ones(n, bool)
    refused = []
    for conj in conjuncts:
        keep = skipping._conjunct_keep(conj, idx.arrow_index, refused)
        if keep is not None:
            ladder &= np.asarray(keep.fill_null(True), bool)
    out["ladder"] = ladder
    out["whole"] = skipping_mask(files, conjuncts, md)
    return out, refused


# ---- the lane ----

def test_a_decimal_column_of_18_digits_or_fewer_has_a_lane():
    assert stat_leaf_types(MONEY) == {
        ("q",): "integer", ("p",): "decimal(7,2)", ("w",): "decimal(18,2)",
        ("z",): "decimal(20,2)"}
    idx = build_index(FILES, metadata=MONEY)
    assert idx.cols == {("p",): (0, "decimal:2"), ("w",): (3, "decimal:2"),
                        ("q",): (6, "int")}
    assert idx.unindexed == {"decimal": 1}      # z: past 18 digits
    assert idx.vals.shape[0] == 10
    assert idx.vals[0, :3].tolist() == [1, 101, 201]            # cents
    assert idx.vals[1, :3].tolist() == [199, 299, 399]
    assert idx.vals[3, :8].tolist() == [BIG * 100 + 70 + i for i in range(8)]
    assert idx.vals[4, :8].tolist() == [BIG * 100 + 71 + i for i in range(8)]
    assert idx.valid[:9, :8].all()
    # the parsed leaf is the column's own type, exact
    assert idx.arrow_index.min_values(("w",)).type == pa.decimal128(18, 2)
    assert idx.arrow_index.min_values(("w",))[3].as_py() == D(
        f"{BIG}.73")
    # without the table's schema a decimal reads as JSON reads it
    plain = build_index(FILES)
    assert plain.cols[("p",)][1] == "float"


def test_no_step_of_the_18_digit_lane_passes_through_a_double():
    """Neighbouring files differ by a cent, 16 digits down: a double
    holds four values to the unit there, so any step through float64
    merges them."""
    idx = build_index(FILES, metadata=MONEY)
    cents = idx.vals[3, :8]
    assert len(set(cents.tolist())) == 8
    assert len({float(D(int(c)) / 100) for c in cents}) < 8   # a double does
    for i in range(8):
        pred = col("w") == lit(D(f"{BIG}.7{i}"))
        got, refused = masks(FILES, pred)
        want = [j in (i - 1, i) for j in range(8)]     # [.7j, .7(j+1)]
        assert not refused
        for route, mask in got.items():
            assert mask.tolist() == want, (route, i)


@pytest.mark.parametrize("kind,want", [
    ("decimal(7,2)", pa.decimal128(7, 2)), ("decimal(18,18)",
                                            pa.decimal128(18, 18)),
    ("decimal(18,0)", pa.decimal128(18, 0)), ("decimal(19,2)", None),
    ("decimal(38,10)", None), ("decimal(5,-2)", None), ("long", None),
    ("double", None), (None, None),
])
def test_which_decimals_get_a_lane(kind, want):
    assert decimal_lane_type(kind) == want


def test_a_stat_with_more_places_than_the_scale_is_unknown_in_its_slot():
    rows = [money_row(i) for i in range(3)] + [
        '{"numRecords":8,"minValues":{"q":1,"p":1.015},'
        '"maxValues":{"q":2,"p":9.5},"nullCount":{"q":0,"p":0}}']
    files = files_of(*rows)
    idx = build_index(files, metadata=MONEY)
    assert idx.cols[("p",)] == (0, "decimal:2")
    assert idx.valid[0, :4].tolist() == [True, True, True, False]   # 1.015
    assert idx.valid[1, :4].tolist() == [True] * 4 and idx.vals[1, 3] == 950
    assert idx.arrow_index.min_values(("p",)).type == pa.decimal128(7, 2)
    # not rounded to 1.02 nor cut to 1.01: the file is kept by its min
    got, _ = masks(files, col("p") < lit(D("0.01")))
    for route, mask in got.items():
        assert mask.tolist() == [False, False, False, True], route
    got, _ = masks(files, col("p") > lit(D("9.50")))    # by its max, exact
    for route, mask in got.items():
        assert mask.tolist() == [False] * 4, route


def test_a_stat_that_is_no_number_reads_as_it_did_before():
    """Text where a number belongs: neither decimal reading takes it,
    and JSON inference refuses a column of numbers and text, as ever."""
    rows = [money_row(0), money_row(1).replace('"p":1.01', '"p":"cheap"')]
    idx = build_index(files_of(*rows), metadata=MONEY)
    assert idx.cols == {} and not idx.has_lanes
    assert skipping_mask(files_of(*rows), [col("q") > lit(100)],
                         MONEY).all()


def test_an_append_carries_the_kind_and_reads_the_tail_exactly():
    seed_files = files_of(*[money_row(i) for i in range(5)])
    seed = build_index(seed_files, metadata=MONEY).seed(np.ones(5, bool))
    live = np.array([True, False, True, True, True, True, True, True])
    tail = pa.chunked_array([pa.array([money_row(i) for i in (5, 6, 7)])])
    idx, attrs = append_index(seed, live, tail, metadata=MONEY)
    assert attrs == {"rows": 3, "dropped": 1}
    full = build_index(files_of(*[money_row(i) for i in (0, 2, 3, 4, 5, 6, 7)]),
                       metadata=MONEY)
    assert idx.cols == full.cols and idx.unindexed == full.unindexed
    assert np.array_equal(idx.vals[:, :7], full.vals[:, :7])
    assert np.array_equal(idx.valid[:, :7], full.valid[:, :7])
    assert idx.arrow_index._table.equals(full.arrow_index._table)
    # a tail stat of more places does not read under the seed's schema:
    # the caller builds in full, and the slot is unknown there
    odd = pa.chunked_array([pa.array(
        [money_row(5).replace('"p":5.01', '"p":5.015')])])
    none, why = append_index(seed, np.array([True] * 6), odd, metadata=MONEY)
    assert none is None and "append_fallback" in why


def test_the_build_span_names_the_lanes_by_kind(tmp_path):
    import delta_tpu.api as dta
    from delta_tpu import Table

    data = pa.table({
        "id": pa.array(range(40), pa.int64()),
        "amount": pa.array([D(i) / 4 for i in range(40)],
                           pa.decimal128(9, 2)),
        "huge": pa.array([D(i) for i in range(40)], pa.decimal128(30, 2))})
    dta.write_table(str(tmp_path), data, mode="error")
    snapshot = Table.for_path(str(tmp_path)).latest_snapshot()
    unindexed = obs.counter("scan.stats_index_unindexed_leaves.decimal")
    before = unindexed.value
    obs.set_trace_mode("on")
    obs.reset_trace_buffer()
    try:
        got = snapshot.scan(filter=col("amount") > lit(D("100"))).file_paths()
        spans = {s.name: s.to_dict()["attrs"]
                 for s in obs.get_finished_spans()}
    finally:
        obs.set_trace_mode("off")
    assert got == []
    assert spans["stats.index_build"]["lane_kinds"] == "int:1,decimal:1"
    assert spans["stats.index_build"]["unindexed"] == 1
    assert unindexed.value == before + 1    # `huge` is still counted
    assert spans["plan.skip"]["decimal_atoms"] == 1
    assert spans["plan.skip"]["atoms"] == 1 and spans["plan.skip"][
        "groups"] == 1 and spans["plan.skip"]["distributed"] == 0


# ---- literals ----

@pytest.mark.parametrize("value,kind,want", [
    (5, "decimal:2", 500), (D("5.01"), "decimal:2", 501),
    (D("5.1"), "decimal:2", 510), ("5.10", "decimal:2", 510),
    (D("-0.35"), "decimal:2", -35), (np.int64(7), "decimal:2", 700),
    (D("5.015"), "decimal:2", None),        # more places than the scale
    (5.1, "decimal:2", None), (5.0, "decimal:2", None),     # a float
    ("cheap", "decimal:2", None), (True, "decimal:2", None),
    (None, "decimal:2", None), (D("NaN"), "decimal:2", None),
    (D("1e30"), "decimal:2", None),         # past an int64
    (D(f"{BIG}.78"), "decimal:2", BIG * 100 + 78),
    (3, "decimal:0", 3), (D("3.5"), "decimal:0", None),
    (D("0.000000000000000001"), "decimal:18", 1),
    # an int lane takes a decimal only where it is whole, and no text
    (D("4000"), "int", 4000), (D("4000.5"), "int", None),
    (D("4e3"), "int", 4000), ("4000", "int", None), (4000.0, "int", None),
    (2**63, "int", None), (D(2**63 - 1), "int", 2**63 - 1),
])
def test_a_literal_is_encoded_exactly_or_not_at_all(value, kind, want):
    assert encode_literal(value, kind) == want


@pytest.mark.parametrize("value,want", [
    (5, D(5)), ("5.10", D("5.10")), (D("5.015"), D("5.015")), (5.1, None),
    (True, None), ("x", None), (D("Infinity"), None), (None, None),
])
def test_what_states_an_exact_decimal(value, want):
    assert decimal_literal(value) == want
    if isinstance(value, float):    # the ladder takes the fraction it is
        assert decimal_literal(value, floats=True) == D(value)


def test_a_fraction_never_meets_an_int_lane_by_truncation():
    """`q < 5.5` over whole quantities is `q <= 5`: truncated to `q < 5`
    it would drop the file whose least is 5."""
    files = files_of(*[money_row(i) for i in range(8)])    # q in [i, i + 3]
    got, refused = masks(files, col("q") < lit(D("5.5")))
    assert not refused and "twin" not in got        # the ladder's, exact
    assert got["ladder"].tolist() == [i <= 5 for i in range(8)]
    assert got["whole"].tolist() == got["ladder"].tolist()
    got, _ = masks(files, col("q") < lit(D("5")))   # whole: on the lanes
    assert "twin" in got
    for route, mask in got.items():
        assert mask.tolist() == [i < 5 for i in range(8)], route


@pytest.mark.parametrize("literal,on_lanes,want", [
    (D("3.50"), True, [i <= 3 for i in range(8)]),
    (3, True, [i <= 2 for i in range(8)]),              # i.01 <= 3
    ("3.01", True, [i <= 3 for i in range(8)]),
    (D("3.005"), False, [i <= 2 for i in range(8)]),    # the ladder, exact
    (3.5, False, [i <= 3 for i in range(8)]),           # the float's fraction
])
def test_a_decimal_conjunct_gives_one_mask_on_every_route(literal, on_lanes,
                                                          want):
    got, refused = masks(FILES, col("p") <= lit(literal))
    assert not refused and ("twin" in got) == on_lanes
    for route, mask in got.items():
        assert mask.tolist() == want, route


def test_a_literal_no_decimal_states_keeps_and_is_counted():
    counted = obs.counter("scan.skip_uncompared_conjuncts")
    before = counted.value
    got, refused = masks(FILES, col("p") <= lit("cheap"))
    assert refused and "twin" not in got
    assert got["whole"].all() and got["ladder"].all()
    assert counted.value == before + 1


# ---- AND under OR ----

def between(name, lo, hi):
    return (col(name) >= lit(lo)) & (col(name) <= lit(hi))


BUCKET = between("q", 2, 7) & (between("p", 3, D("3.50"))
                               | between("w", D(f"{BIG}.75"), D(f"{BIG}.76")))


def test_an_or_over_ands_is_distributed_into_or_groups():
    idx = build_index(FILES, metadata=MONEY)
    distributed = obs.counter("scan.skip_disjunctions_distributed")
    before = distributed.value
    block, fallback = compile_conjuncts(split_conjuncts(BUCKET), idx)
    assert not fallback and distributed.value == before + 1
    # q >=, q <=, and (p1 OR w1), (p1 OR w2), (p2 OR w1), (p2 OR w2)
    assert (block.n_atoms, block.n_groups) == (10, 6)
    assert (block.decimal_atoms, block.distributed) == (8, 1)
    assert block.grp.tolist() == [0, 1, 2, 2, 3, 3, 4, 4, 5, 5]
    got, refused = masks(FILES, BUCKET)
    # p admits files 2, 3; w files 4, 5, 6; q all of those
    want = [i in (2, 3, 4, 5, 6) for i in range(8)]
    assert not refused and set(got) == {"twin", "kernel", "ladder", "whole"}
    for route, mask in got.items():
        assert mask.tolist() == want, route


def test_query_28s_bucket_is_28_atoms_in_12_groups():
    md = metadata_of(("d", "integer"), ("q", "integer"),
                     ("lp", "decimal(7,2)"), ("ca", "decimal(7,2)"),
                     ("wc", "decimal(7,2)"))
    row = ('{"numRecords":8,"minValues":{"d":1,"q":1,"lp":1.00,"ca":0.00,'
           '"wc":1.00},"maxValues":{"d":2,"q":9,"lp":9.00,"ca":5.00,'
           '"wc":9.00}}')
    idx = build_index(files_of(row), metadata=md)
    pred = between("d", 1, 6) & between("q", 0, 5) & (
        between("lp", 8, 18) | between("ca", 459, 1459)
        | between("wc", 57, 77))
    block, fallback = compile_conjuncts(split_conjuncts(pred), idx)
    assert not fallback
    assert (block.n_atoms, block.n_groups, block.decimal_atoms) == (28, 12,
                                                                    24)
    assert 1 + len(np.unique(np.concatenate(
        [block.rows_mn, block.rows_mx, block.rows_nc]))) == 16


def wide_or(terms):
    pred = None
    for k in range(terms):
        one = between("p", k, k + 1)
        pred = one if pred is None else pred | one
    return pred


@pytest.mark.parametrize("terms,atoms", [(2, 8), (3, 24), (4, 64)])
def test_a_distribution_within_the_atom_limit_runs_on_the_lanes(terms, atoms):
    idx = build_index(FILES, metadata=MONEY)
    block, fallback = compile_conjuncts([wide_or(terms)], idx)
    assert not fallback and block.n_atoms == atoms <= IN_LIST_ATOM_LIMIT
    got, _ = masks(FILES, wide_or(terms))
    for route, mask in got.items():
        assert mask.tolist() == got["ladder"].tolist(), route
    assert got["ladder"].tolist() == [i < terms for i in range(8)]


def test_a_distribution_past_the_atom_limit_is_the_ladders():
    idx = build_index(FILES, metadata=MONEY)
    too_wide = obs.counter("scan.skip_disjunctions_too_wide")
    distributed = obs.counter("scan.skip_disjunctions_distributed")
    before = too_wide.value, distributed.value
    pred = wide_or(5)       # 32 groups of 5 atoms
    block, fallback = compile_conjuncts([pred, col("q") >= lit(1)], idx)
    assert len(fallback) == 1 and block.n_atoms == 1
    assert (too_wide.value, distributed.value) == (before[0] + 1, before[1])
    got, refused = masks(FILES, pred)
    assert not refused and "twin" not in got
    assert got["ladder"].tolist() == got["whole"].tolist() == [
        i < 5 for i in range(8)]


def test_a_side_that_does_not_compile_sends_the_conjunct_to_the_ladder():
    idx = build_index(FILES, metadata=MONEY)
    too_wide = obs.counter("scan.skip_disjunctions_too_wide")
    before = too_wide.value
    pred = between("p", 3, D("3.50")) | between("z", 1, 2)  # z: no lane
    block, fallback = compile_conjuncts([pred], idx)
    assert block is None and len(fallback) == 1
    assert too_wide.value == before


def test_on_the_ladder_an_and_prunes_by_either_side_and_a_side_with_no_answer_keeps():
    index = StatsIndex.from_stats_column(FILES.column("stats"),
                                         leaf_types=stat_leaf_types(MONEY))

    def keep(pred):
        out = skipping._conjunct_keep(pred, index, [])
        return None if out is None else out.fill_null(True).to_pylist()

    p3 = col("p") <= lit(D("3.50"))             # files 0..3
    q5 = col("q") >= lit(5)                     # files 2..7
    nothing = col("missing") > lit(1)           # no stats: no answer
    assert keep(p3 & q5) == [i in (2, 3) for i in range(8)]
    assert keep(p3 & nothing) == keep(p3)       # upstream's DataSkippingReader
    assert keep(nothing & nothing) is None
    assert keep((p3 & q5) | (col("q") <= lit(0))) == [
        i in (0, 2, 3) for i in range(8)]
    assert keep(p3 | nothing) is None           # an OR needs both


def test_the_planner_gives_one_set_of_files_on_both_routes(tmp_path,
                                                           monkeypatch):
    """Through the public API: a written table of decimal(18,2), Query
    28's shape of filter, HostEngine, the twin and the kernel. (Amounts
    a double holds: this library's own writer still leaves a decimal's
    stats through `float`.)"""
    import delta_tpu.api as dta
    from delta_tpu import Table
    from delta_tpu.engine.host import HostEngine
    from delta_tpu.engine.tpu import TpuEngine

    some = 1000
    for k in range(6):      # six files: quantity 10k.., price some + k + cents
        data = pa.table({
            "q": pa.array([10 * k, 10 * k + 5], pa.int32()),
            "price": pa.array([D(f"{some + k}.25"), D(f"{some + k}.75")],
                              pa.decimal128(18, 2))})
        dta.write_table(str(tmp_path), data,
                        mode="error" if k == 0 else "append")
    pred = between("q", 0, 100) & (
        between("price", D(f"{some + 1}.76"), D(f"{some + 2}.24"))
        | between("price", some + 4, D(f"{some + 4}.25")))
    found = {}
    for name, engine, route in (("host", HostEngine(), "off"),
                                ("twin", TpuEngine(), "off"),
                                ("kernel", TpuEngine(), "force")):
        monkeypatch.setenv("DELTA_TPU_DEVICE_SKIP", route)
        snapshot = Table.for_path(str(tmp_path), engine).latest_snapshot()
        scan = snapshot.scan(filter=pred)
        files = scan.add_files_table()
        found[name] = sorted(json.loads(s)["minValues"]["q"]
                             for s in files.column("stats").to_pylist())
    # the first range lies between file 1's most and file 2's least, by a
    # cent on either side; the second takes file 4 by its least alone
    assert found == {"host": [40], "twin": [40], "kernel": [40]}
