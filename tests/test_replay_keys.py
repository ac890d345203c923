"""`build_replay_keys` (PR 42): the path codes are
`pd.factorize(paths, sort=False)`'s element for element however the
column is laid out and however its rows deal to buckets
(`replay/path_codes.py`), the span says how the coding engaged, the
names writers give deal evenly, and a checkpoint that holds a
`(path, dvId)` twice still loads with that file once, last-wins."""

import json
import os
import uuid

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import delta_tpu.api as dta
from delta_tpu import Table, obs
from delta_tpu.engine.host import HostEngine
from delta_tpu.engine.tpu import TpuEngine
from delta_tpu.replay import path_codes
from delta_tpu.replay.state import build_replay_keys

REAL = path_codes.DEAL_MIN_ROWS     # the size at which it starts to deal


def cell_names(ids):
    return [f"part-{i:010d}.parquet" for i in ids]


def spark_names(n, prefix=""):
    rng = np.random.default_rng(n)
    return [f"{prefix}part-{i % 200:05d}-"
            f"{uuid.UUID(bytes=rng.bytes(16))}-c000.snappy.parquet"
            for i in range(n)]


def shuffled(n, seed=0):
    return np.random.default_rng(seed).permutation(n)


def bucket_sizes(column, buckets):
    """How many rows of an Arrow string array each bucket is dealt."""
    offsets, words = path_codes._string_buffers(column)
    order, bounds = path_codes._deal(offsets, words, 0, len(column), buckets)
    assert sorted(order.tolist()) == list(range(len(column)))
    return np.diff(bounds)


def _distinct():
    return pa.chunked_array([cell_names(shuffled(1000))])


def _repeats():
    """Adds, removes and re-adds of the same 150 paths."""
    rng = np.random.default_rng(1)
    return pa.chunked_array([cell_names(rng.integers(0, 150, 1000))])


def _chunked():
    names = cell_names(np.random.default_rng(2).integers(0, 700, 1000))
    return pa.chunked_array([names[:1], names[1:400], [], names[400:]],
                            pa.string())


def _sliced():
    ids = np.random.default_rng(3).integers(0, 700, 1200)
    names = pa.array(cell_names(ids))
    return pa.chunked_array([names.slice(137, 1000)])


def _unequal():
    """Lengths 0..40, the empty string, strings under four bytes, and
    multi-byte UTF-8 round the positions the dealing reads."""
    rng = np.random.default_rng(4)
    pool = (["", "a", "ab", "abc", "abcd", "é", "éé", "日本",
             "日本語.parquet",
             "données/part-é.parquet", "x" * 25, "x" * 26]
            + ["".join(rng.choice(list("ab/é日-0"), size=k))
               for k in rng.integers(0, 40, 300)])
    picks = rng.integers(0, len(pool), 1000)
    return pa.chunked_array([[pool[i] for i in picks]])


def _one_bucket():
    """Every string equal in length, round its middle and 22 from its
    end: one bucket holds all, and the answer is still exact."""
    ids = np.random.default_rng(5).integers(0, 600, 1000)
    names = [f"{i:06d}-part-same-middle-and-same-tail.snappy.parquet"
             for i in ids]
    column = pa.chunked_array([names])
    assert bucket_sizes(column.chunk(0), 64).max() == 1000
    assert len(set(names)) > 400
    return column


def _large_string():
    return pa.chunked_array(
        [pa.array(cell_names(shuffled(1000, 6) % 800), pa.large_string())])


def _with_nulls():
    names = cell_names(np.random.default_rng(7).integers(0, 300, 1000))
    for i in (0, 17, 500, 999):
        names[i] = None
    return pa.chunked_array([pa.array(names, pa.string())])


def _real(n):
    """`n` rows at the real threshold: three in eight repeat."""
    return lambda: pa.chunked_array(
        [cell_names(shuffled(n, n) % (n * 5 // 8 + 1))])


# name -> (the column, whether it is coded with the buckets made small
# enough that a thousand rows deal)
COLUMNS = {
    "all_distinct": (_distinct, True),
    "heavy_repeats": (_repeats, True),
    "chunked": (_chunked, True),
    "sliced_nonzero_offset": (_sliced, True),
    "unequal_lengths_empty_multibyte": (_unequal, True),
    "every_row_in_one_bucket": (_one_bucket, True),
    "large_string": (_large_string, True),
    "null_paths": (_with_nulls, True),
    "small_table_one_table": (_repeats, False),
    "n_0": (lambda: pa.chunked_array([], pa.string()), False),
    "n_1": (lambda: pa.chunked_array([["part-0000000001.parquet"]]), False),
    "just_under_the_dealing_size": (_real(REAL - 1), False),
    "at_the_dealing_size": (_real(REAL), False),
    "just_over_the_dealing_size": (_real(REAL + 1), False),
    "spark_names": (lambda: pa.chunked_array([spark_names(1000)]), True),
}


@pytest.fixture
def small_buckets(monkeypatch):
    monkeypatch.setattr(path_codes, "DEAL_MIN_ROWS", 64)
    monkeypatch.setattr(path_codes, "ROWS_PER_BUCKET", 32)


def keys_table(paths, dv_ids=None):
    n = len(paths)
    if dv_ids is None:
        dv_ids = pa.nulls(n, pa.string())
    return pa.table({"path": paths, "dv_id": dv_ids})


def factorized(column):
    return pd.factorize(column.to_pandas(), sort=False)[0].astype(np.uint32)


@pytest.mark.parametrize("name", list(COLUMNS))
def test_path_codes_are_pd_factorizes(name, request):
    make, small = COLUMNS[name]
    if small:
        request.getfixturevalue("small_buckets")
    column = make()
    path_code, dv_code = build_replay_keys(keys_table(column))
    assert path_code.dtype == dv_code.dtype == np.uint32
    np.testing.assert_array_equal(path_code, factorized(column))
    assert not dv_code.any() and len(dv_code) == len(column)
    dealt = path_codes.bucket_count(len(column)) > 1
    assert dealt == (small or len(column) >= REAL)


def test_a_dv_lane_beside_the_paths(small_buckets):
    """Some rows carry a deletion vector: 0 where there is none, 1 + the
    first-appearance code of the id where there is, the paths untouched."""
    rng = np.random.default_rng(8)
    column = pa.chunked_array([cell_names(rng.integers(0, 200, 1000))])
    ids = [None if i % 3 else f"u{rng.integers(0, 40):03d}"
           for i in range(1000)]
    path_code, dv_code = build_replay_keys(
        keys_table(column, pa.array(ids, pa.string())))
    np.testing.assert_array_equal(path_code, factorized(column))
    want = pd.factorize(pd.Series(ids, dtype=object), sort=False,
                        use_na_sentinel=True)[0] + 1
    np.testing.assert_array_equal(dv_code, want.astype(np.uint32))
    assert dv_code[1] == 0 and dv_code[0] == 1


@pytest.mark.parametrize("n,dealt", [(1000, False), (REAL + 900, True)])
def test_the_span_says_how_it_engaged(n, dealt):
    column = pa.chunked_array([cell_names(shuffled(n, 9) % (n - 400))])
    obs.set_trace_mode("verbose")
    obs.reset_trace_buffer()
    try:
        build_replay_keys(keys_table(column))
        spans = {s.name: s.to_dict() for s in obs.get_finished_spans()}
    finally:
        obs.set_trace_mode(None)
        obs.reset_trace_buffer()
    assert "keys.to_pandas" not in spans and "keys.combine" in spans
    attrs = spans["keys.factorize"]["attrs"]
    assert attrs["rows"] == n and attrs["uniques"] == n - 400
    if dealt:
        assert attrs["buckets"] == path_codes.bucket_count(n) > 1
        assert 1 <= attrs["threads"] <= attrs["buckets"]
        assert n / attrs["buckets"] <= attrs["largest_bucket_rows"] < n
    else:
        assert (attrs["buckets"], attrs["threads"],
                attrs["largest_bucket_rows"]) == (1, 1, n)


def test_loads_on_many_threads_share_the_pool(small_buckets):
    """More callers than cores code columns of their own at once through
    the one pool, the interpreter switching threads as often as it can:
    every caller gets `pd.factorize`'s codes for its own column."""
    import sys
    import threading

    columns = [pa.chunked_array([cell_names(
        np.random.default_rng(k).integers(0, 500 + 37 * k, 3000))])
        for k in range(2 * (os.cpu_count() or 4))]
    got = [None] * len(columns)

    def load(k):
        for _ in range(5):
            got[k] = build_replay_keys(keys_table(columns[k]))[0]

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        callers = [threading.Thread(target=load, args=(k,))
                   for k in range(len(columns))]
        for t in callers:
            t.start()
        for t in callers:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in callers)
    for column, codes in zip(columns, got):
        np.testing.assert_array_equal(codes, factorized(column))


@pytest.mark.parametrize("names", [
    "cells", "spark", "spark_under_hive_partitions",
    "spark_under_a_long_prefix"])
def test_the_names_writers_give_deal_evenly(names):
    """No bucket of 64 gets more than twice its share of 40,000 names:
    the generator's `part-<10 digits>.parquet`, Spark's, and Spark's
    behind partition directories short and long."""
    n, buckets = 40_000, 64
    column = pa.array({
        "cells": lambda: cell_names(shuffled(4 * n, 10)[:n]),
        "spark": lambda: spark_names(n),
        "spark_under_hive_partitions": lambda: spark_names(
            n, "event_date=2024-01-05/region=emea/"),
        "spark_under_a_long_prefix": lambda: spark_names(
            n, "tenant=0123456789abcdef/year=2024/month=01/day=05/hour=23/"),
    }[names]())
    sizes = bucket_sizes(column, buckets)
    assert sizes.max() <= 2 * n / buckets and sizes.min() > 0


@pytest.mark.parametrize("engine", [TpuEngine, HostEngine])
def test_a_checkpoint_that_repeats_a_file_yields_it_once_last_wins(
        tmp_path, engine):
    """What keying only the commits behind a checkpoint would change: the
    checkpoint's own rows are replayed too, so a `(path, dvId)` it holds
    twice (sizes 111, then 222) is one live file, the later row's."""
    path = str(tmp_path / "t")
    for i, mode in enumerate(["error", "append"]):
        dta.write_table(path, pa.table({"x": pa.array([i], pa.int64())}),
                        mode=mode, engine=HostEngine())
    Table.for_path(path, HostEngine()).checkpoint()
    log = os.path.join(path, "_delta_log")
    [name] = [f for f in os.listdir(log) if f.endswith(".checkpoint.parquet")]
    ckpt = pq.read_table(os.path.join(log, name))
    rows = ckpt.to_pylist()
    at = next(i for i, row in enumerate(rows) if row["add"] is not None)
    again = json.loads(json.dumps(rows[at]))
    rows[at]["add"]["size"], again["add"]["size"] = 111, 222
    dup = again["add"]["path"]
    pq.write_table(pa.Table.from_pylist(rows + [again], schema=ckpt.schema),
                   os.path.join(log, name))
    dta.write_table(path, pa.table({"x": pa.array([2], pa.int64())}),
                    mode="append", engine=HostEngine())

    snap = Table.for_path(path, engine()).latest_snapshot()
    live = snap.state.add_files_table
    paths = live.column("path").to_pylist()
    assert snap.version == 2
    assert paths.count(dup) == 1 and len(paths) == len(set(paths)) == 3
    assert live.column("size")[paths.index(dup)].as_py() == 222
