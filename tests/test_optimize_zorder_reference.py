"""OPTIMIZE ... ZORDER BY against the plain reference
(`chipbench/reference/zorder_oracle.py`: numpy and pyarrow alone), row
for row, at small sizes on three seeds: the command's output is the
bin's files in ascending order of path, ranked densely and stably (a
null as 0), interleaved and sorted totally, whatever the order in which
the replay hands the files over."""

import decimal
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import delta_tpu.api as dta
from chipbench.reference import zorder_oracle as oracle
from delta_tpu import Table, obs
from delta_tpu.commands import optimize as optimize_mod
from delta_tpu.expressions import col, lit
from delta_tpu.ops import zorder
from delta_tpu.ops.replay import pad_bucket

SEEDS = (3, 2**31 + 11, 77)
BY = ("a", "b", "c")


def rows(n, rng, part, how):
    """`n` rows of one partition. `how` shapes the three key columns."""
    a = rng.integers(1, 50_000, n).astype(np.int32)
    b = rng.integers(1, 2**31, n).astype(np.int64)
    c = rng.integers(1, 720_000_000, n).astype(np.int64)
    a_nulls = None
    if how == "nulls":
        a_nulls = rng.random(n) < 0.2
    elif how == "ties":
        a = rng.integers(1, 4, n).astype(np.int32)
        b = rng.integers(1, 3, n).astype(np.int64)
        c = rng.integers(1, 5, n).astype(np.int64)
    elif how == "wide":
        # negative keys, and long keys more than 2^32 apart: the column's
        # order reaches the chip as host ranks (`_to_sortable_u32`)
        a = rng.integers(-40_000, 40_000, n).astype(np.int32)
        b = rng.integers(-2**40, 2**40, n).astype(np.int64)
        c = rng.integers(-5, 5, n).astype(np.int64) * (1 << 33)
    cents = rng.integers(-10_000, 100_000, n)
    return pa.table({
        "part": pa.array(np.full(n, part, np.int32)),
        "a": pa.array(a, pa.int32(), mask=a_nulls),
        "b": pa.array(b, pa.int64()),
        "c": pa.array(c, pa.int64()),
        "paid": pa.array([decimal.Decimal(int(v)).scaleb(-2) for v in cents],
                         pa.decimal128(7, 2),
                         mask=rng.random(n) < 0.1),
        "note": pa.array([f"row {i}" for i in rng.integers(0, 1000, n)]),
    })


def landed(path, rng, n, how, files=7):
    """A table of two partitions: 1, in `files` files of several commits,
    and 2, one file, which no predicate here matches."""
    dta.write_table(path, rows(300, rng, 2, how), mode="error",
                    partition_by=["part"])
    cuts = np.sort(rng.choice(np.arange(1, n), files - 1, replace=False))
    whole = rows(n, rng, 1, how)
    for lo, hi in zip(np.r_[0, cuts], np.r_[cuts, n]):
        dta.write_table(path, whole.slice(int(lo), int(hi - lo)),
                        mode="append")
    return Table.for_path(path)


def held_to_the_reference(table, metrics, n_out=1):
    """The commit at `metrics.version` against the reference's reading
    of the log and the files; returns the commit's adds."""
    log = os.path.join(table.path, "_delta_log")
    version = metrics.version
    before = oracle.replay(log, version - 1)
    inputs = oracle.in_partition(before, "part", 1)
    commit = oracle.read_commit(log, version)
    adds = [x["add"] for x in commit if "add" in x]
    removes = [x["remove"] for x in commit if "remove" in x]
    [info] = [x["commitInfo"] for x in commit if "commitInfo" in x]
    assert info["operation"] == "OPTIMIZE"
    assert sorted(r["path"] for r in removes) == [a["path"] for a in inputs]
    assert not any(x["dataChange"] for x in adds + removes)
    assert all(a["partitionValues"] == {"part": "1"} for a in adds)
    assert len(adds) == n_out == metrics.num_files_added
    assert metrics.num_files_removed == len(inputs)
    rows_in = oracle.read_files(table.path, [a["path"] for a in inputs])
    want = oracle.expected_files(rows_in, BY, n_out)
    got = [pq.read_table(os.path.join(table.path, a["path"])) for a in adds]
    assert len(got) == len(want)
    for g, w, add in zip(got, want, adds):
        assert g.equals(w)      # row for row, every column, nulls as nulls
        assert oracle.stated_stats(add, g.schema) == oracle.file_stats(g)
    # the other partition stands untouched
    after = oracle.replay(log, version)
    assert oracle.in_partition(after, "part", 2) == oracle.in_partition(
        before, "part", 2)
    assert [a["path"] for a in oracle.in_partition(after, "part", 1)] == sorted(
        a["path"] for a in adds)
    return adds


def run_zorder(table, **kwargs):
    return (table.optimize().where(col("part") == lit(1))
            .execute_zorder_by(*BY, **kwargs))


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("how", ["plain", "nulls", "ties", "wide"])
def test_the_output_is_the_references_row_for_row(tmp_path, seed, how):
    rng = np.random.default_rng(seed)
    table = landed(str(tmp_path / "t"), rng, 1500, how)
    held_to_the_reference(table, run_zorder(table))
    # "Z-Ordering is not idempotent": a second run rewrites the file the
    # first wrote, and is held to the same
    held_to_the_reference(table, run_zorder(table))


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("n", [1024, 1025], ids=["a-bucket", "one-over"])
def test_a_bin_of_a_bucket_and_of_one_row_more(tmp_path, seed, n):
    """No padding row at `n` = the bucket; at one over it as many
    padding rows as real ones, all of which rank above every real row."""
    assert pad_bucket(n, min_bucket=1024) == (1024 if n == 1024 else 2048)
    rng = np.random.default_rng(seed)
    table = landed(str(tmp_path / "t"), rng, n, "nulls", files=4)
    held_to_the_reference(table, run_zorder(table))


@pytest.mark.parametrize("seed", SEEDS)
def test_two_output_files_are_the_order_cut_in_two(tmp_path, seed):
    rng = np.random.default_rng(seed)
    table = landed(str(tmp_path / "t"), rng, 1501, "plain")
    live = oracle.replay(os.path.join(table.path, "_delta_log"), 7)
    size = sum(a["size"] for a in oracle.in_partition(live, "part", 1))
    adds = held_to_the_reference(
        table, run_zorder(table, max_file_size=size // 2 + 1), n_out=2)
    assert [oracle.stated_rows(a) for a in adds] == [751, 750]


@pytest.mark.parametrize("seed", SEEDS)
def test_files_handed_over_in_any_order_give_the_sorted_by_path_result(
        tmp_path, seed, monkeypatch):
    """The replay's order of the live files is no part of the result."""
    rng = np.random.default_rng(seed)
    table = landed(str(tmp_path / "t"), rng, 1200, "ties")
    real = optimize_mod._rewrite_bin
    handed = []

    def shuffled(table_, snapshot, bin_files, *rest):
        order = rng.permutation(len(bin_files))
        handed.append([bin_files[i].path for i in order])
        return real(table_, snapshot, [bin_files[i] for i in order], *rest)

    monkeypatch.setattr(optimize_mod, "_rewrite_bin", shuffled)
    held_to_the_reference(table, run_zorder(table))
    assert handed and handed[0] != sorted(handed[0])


def test_the_command_says_what_it_did_in_spans_and_counters(tmp_path):
    rng = np.random.default_rng(5)
    table = landed(str(tmp_path / "t"), rng, 1100, "plain", files=5)
    names = ("optimize.bins", "optimize.rows_clustered",
             "optimize.files_removed", "optimize.files_added")
    before = [obs.counter(n).value for n in names]
    obs.set_trace_mode("on")
    obs.set_device_obs_mode("on")
    obs.reset_trace_buffer()
    obs.reset_device_obs()
    try:
        metrics = run_zorder(table)
        spans = [s.to_dict() for s in obs.get_finished_spans()]
        records = [r for r in obs.get_dispatch_records()
                   if r["kernel"] == "zorder.curve_perm"]
    finally:
        obs.set_trace_mode(None)
        obs.set_device_obs_mode(None)
        obs.reset_trace_buffer()
        obs.reset_device_obs()
    assert [obs.counter(n).value - b for n, b in zip(names, before)] == [
        1, 1100, 5, 1]
    by_name = {s["name"]: s for s in spans}
    [top] = [s for s in spans if s["name"] == "command.optimize"]
    order = ["optimize.plan", "optimize.read", "optimize.keys",
             "optimize.curve", "optimize.gather", "optimize.write",
             "optimize.commit"]
    mine = sorted((s for s in spans if s["name"] in order),
                  key=lambda s: s["start_unix_ns"])
    assert [s["name"] for s in mine] == order
    assert all(s["parent_id"] == top["span_id"] for s in mine)
    attrs = {name: by_name[name]["attrs"] for name in order}
    assert attrs["optimize.plan"] == {"candidates": 5, "bins": 1}
    assert attrs["optimize.read"]["files"] == 5
    assert attrs["optimize.read"]["rows"] == 1100
    assert attrs["optimize.read"]["bytes"] == metrics.bytes_removed
    assert attrs["optimize.keys"] == {"columns": 3, "rows": 1100,
                                      "n_pad": 2048}
    assert attrs["optimize.curve"] == {"curve": "zorder", "n_pad": 2048}
    assert attrs["optimize.gather"]["rows"] == 1100
    assert attrs["optimize.gather"]["columns"] == 6
    assert attrs["optimize.write"] == {"rows": 1100, "files": 1,
                                       "bytes": metrics.bytes_added}
    assert attrs["optimize.commit"] == {"adds": 1, "removes": 5}
    [record] = records
    assert record["attrs"] == {"columns": 3, "n_pad": 2048, "rows": 1100}
    assert record["h2d_bytes"] == 3 * 2048 * 4
    assert record["d2h_bytes"] == 2048 * 4


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("curve", ["zorder", "hilbert"])
def test_the_one_sort_loop_gives_the_stable_sorts_permutation(seed, curve):
    """`_curve_perm`, whose every sort is one two-operand sort in one
    loop, against the program it replaced (three stable `argsort`s with
    their scatters, one stable multi-operand `lax.sort`: `range_rank`,
    the key words, `curve_order`), bit for bit, ties and padding
    included."""
    import jax.numpy as jnp

    def was(stacked):
        m = stacked.shape[1]
        ranks = jnp.stack([zorder.range_rank(lane) for lane in stacked])
        return zorder.curve_order(zorder._curve_keys(ranks, m, curve))

    rng = np.random.default_rng(seed)
    n, m = 2500, 4096
    stacked = np.full((3, m), 0xFFFFFFFF, np.uint32)
    stacked[0, :n] = rng.integers(0, 4, n)              # ties
    stacked[1, :n] = rng.integers(0, 2**32, n)
    stacked[2, :n] = rng.integers(2**32 - 3, 2**32, n)  # ties with padding
    got = np.asarray(zorder._curve_perm(jnp.asarray(stacked), curve))
    assert np.array_equal(got, np.asarray(was(jnp.asarray(stacked))))
