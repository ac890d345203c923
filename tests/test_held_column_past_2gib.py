"""A held table with a `string` column past 2 GiB (`replay/state.py`,
`replay/columnar.py`, `stats/skipping.py`). An Arrow `string` column's
offsets are 32-bit a chunk, so such a column lives only as chunks, and
`combine_chunks`, `Table.take` and `concat_arrays` raise over it: the
stats strings of a table at a fact table's width (1.7 KB a file, 2.4M
files: `tpcds-store-sales-4m-stream`). The table refreshes and builds
its index all the same; a narrower table takes the calls it always took.

The big table here is a real log's snapshot whose stats strings are
swapped for the same JSON with 64 KB of blanks inside, the chunks of the
column sharing one buffer, so that the column counts 2.4 GB and holds
0.5. No step copies the column whole (the refresh and the full build of
its index take ~8 s here, the fixture 4), so the tests on it are no
`slow` ones; the same lines also run at a test's size below them, with
the limits patched down."""

import json
import time

import numpy as np
import pyarrow as pa
import pytest

from chipbench.gen import deltalog, deltastream
from delta_tpu import Table, obs
from delta_tpu.expressions import col, lit
from delta_tpu.replay import columnar, state as state_mod
from delta_tpu.stats.skipping import StatsIndex

PARAMS = dict(commits=600, actions_per_commit=100, remove_fraction=0.2,
              checkpoint_interval=10, retained_commits=20, staged_commits=4)
BLANKS = 1 << 16            # a row
CHUNK_ROWS = 7000           # 0.46 GB a chunk
W = deltastream.batch_width(80)
TIME_LIMIT_S = 120          # of the refresh and the plan; they take ~8


def fat_stats(n: int) -> pa.ChunkedArray:
    """`n` stats strings in chunks of `CHUNK_ROWS` that share one buffer:
    row i's stats are row `i % CHUNK_ROWS`'s, `x` in [1000 k, 1000 k + 999]
    for k = i % CHUNK_ROWS."""
    rows = ['{%s"numRecords":10,"minValues":{"x":%d},"maxValues":{"x":%d},'
            '"nullCount":{"x":0}}' % (" " * BLANKS, 1000 * k, 1000 * k + 999)
            for k in range(CHUNK_ROWS)]
    chunk = pa.array(rows, pa.string())
    whole, rest = divmod(n, CHUNK_ROWS)
    return pa.chunked_array([chunk] * whole
                            + ([chunk.slice(0, rest)] if rest else []))


def with_fat_stats(snapshot):
    """The snapshot's held rows with their stats swapped; returns the
    bytes the column counts."""
    state = snapshot.state
    held = state.file_actions
    at = held.schema.get_field_index("stats")
    stats = fat_stats(held.num_rows)
    state.file_actions_raw = pa.Table.from_batches(
        held.set_column(at, "stats", stats).to_batches(), schema=held.schema)
    state._add_table_cache = state._live_rows_cache = None
    return stats.nbytes


@pytest.fixture(scope="module")
def fat(tmp_path_factory):
    m = deltastream.generate(str(tmp_path_factory.mktemp("fat")), PARAMS,
                             seed=5)
    table = Table.for_path(m.table_path)
    snapshot = table.latest_snapshot()
    nbytes = with_fat_stats(snapshot)
    return m, table, snapshot, nbytes


def test_arrow_cannot_concatenate_such_a_column(fat):
    """The premise (pyarrow 25.0): what the parent did on the held
    table raises over this one."""
    _, _, snapshot, nbytes = fat
    held = snapshot.state.file_actions
    assert nbytes > 1 << 31 and held.column("stats").num_chunks >= 5
    with pytest.raises(pa.ArrowInvalid, match="offset overflow"):
        held.column("stats").combine_chunks()
    with pytest.raises(pa.ArrowInvalid, match="offset overflow"):
        held.take(pa.array([3, held.num_rows - 2], pa.int64()))
    assert len(held.filter(pa.array(np.arange(held.num_rows) % 9 == 0))) > 0


def test_the_table_refreshes_and_builds_its_index(fat):
    m, table, snapshot, _ = fat
    held = snapshot.state.file_actions.num_rows
    obs.set_trace_mode("verbose")
    obs.reset_trace_buffer()
    start = time.perf_counter()
    try:
        m.land(1)
        fresh = table.update()
        got = fresh.scan(filter=(col("x") >= lit(2_000_000))
                         & (col("x") < lit(2_005_000))).file_paths()
        took = time.perf_counter() - start
        spans = {s.name: s.to_dict()["attrs"]
                 for s in obs.get_finished_spans()}
    finally:
        obs.set_trace_mode("off")
    assert fresh.version == m.version
    assert spans["table.update"]["outcome"] == "advanced"
    assert spans["advance.probe"]["candidates"] == 20 == spans[
        "advance.probe"]["cleared"]
    assert spans["stats.index_build"]["mode"] == "full"
    assert spans["stats.index_build"]["lanes"] == 4
    # the held rows' stats are row `i % CHUNK_ROWS`'s; the landed
    # commit's are its own (x a batch's width from 451 W: past the range)
    live = np.flatnonzero(fresh.state.live_mask)
    want = sorted(p for i, p in zip(
        live, fresh.state.live_columns(["path"]).column(0).to_pylist())
        if i < held and 2000 <= i % CHUNK_ROWS <= 2004)
    assert len(want) >= 10 and sorted(got) == want
    idx = fresh.state.stats_index
    n = idx.n
    assert n == len(live) == m.num_files()
    kept = live[live < held] % CHUNK_ROWS
    assert np.array_equal(idx.vals[0, :len(kept)], 1000 * kept)
    assert np.array_equal(idx.vals[1, :len(kept)], 1000 * kept + 999)
    assert idx.valid[:, :n].all()
    assert took < TIME_LIMIT_S
    # and again, from the seed: the next refresh appends to the index
    m.land(1)
    fresh = table.update()
    appends = obs.counter("scan.stats_index_appends")
    before = appends.value
    again = fresh.scan(filter=(col("x") >= lit(2_000_000))
                       & (col("x") < lit(2_005_000))).file_paths()
    assert appends.value == before + 1
    assert set(again) <= set(want) and len(again) >= len(want) - 2


def test_a_checkpoints_rows_past_the_limit_come_in_pieces():
    """The first load: `_extract_file_actions` over an `add` column
    whose stats pass what one chunk holds."""
    n = 5 * CHUNK_ROWS + 11
    stats = fat_stats(n)
    ids = np.arange(n)
    chunks, at = [], 0
    for chunk in stats.chunks:
        these = ids[at:at + len(chunk)]
        at += len(chunk)
        chunks.append(pa.StructArray.from_arrays(
            [pa.array([deltalog.path_of(int(i)) for i in these]),
             pa.array(np.full(len(these), 7, np.int64)), chunk],
            names=["path", "size", "stats"]))
    add = pa.chunked_array(chunks)
    assert add.nbytes > 1 << 31
    got = columnar._extract_file_actions(
        pa.table({"add": add}), "add", np.full(n, 9, np.int64),
        np.arange(n, dtype=np.int32))
    assert got.num_rows == n and got.column("stats").num_chunks >= 3
    assert got.schema == columnar.CANONICAL_FILE_ACTION_SCHEMA
    assert got.column("path")[n - 1].as_py() == deltalog.path_of(n - 1)
    assert got.column("order").to_numpy()[-3:].tolist() == [n - 3, n - 2,
                                                            n - 1]
    assert json.loads(got.column("stats")[n - 1].as_py())["minValues"] == {
        "x": 1000 * ((n - 1) % CHUNK_ROWS)}


def test_the_stats_parse_piece_by_piece_to_the_table_one_parse_gives(
        monkeypatch):
    """At a test's size, the piece limit patched down: the pieces'
    tables are the whole column's, whatever each piece inferred."""
    from delta_tpu.stats import skipping

    rows = ['{"numRecords":10,"minValues":{"x":%d},"maxValues":{"x":%d}}'
            % (k, k + 5) for k in range(40)]
    rows[7] = None                                      # no stats
    rows[33] = '{"numRecords":10,"minValues":{"x":1.5},"maxValues":{"x":9}}'
    rows += ['{"numRecords":3,"minValues":{"x":2,"s":"a"},'
             '"maxValues":{"x":4,"s":"b"}}']            # a leaf of its own
    column = pa.chunked_array([pa.array(rows[:25]), pa.array(rows[25:])])
    whole = StatsIndex.from_stats_column(column)._table
    monkeypatch.setattr(skipping, "_PARSE_PIECE_BYTES", 300)
    by_piece = StatsIndex.from_stats_column(column)._table
    assert by_piece.num_rows == 41 and by_piece.column(0).num_chunks > 3
    assert by_piece.schema.equals(whole.schema)
    assert by_piece.combine_chunks().equals(whole.combine_chunks())
    x = by_piece.column("minValues").combine_chunks().field("x")
    assert x.type == pa.float64() and x[33].as_py() == 1.5      # promoted


@pytest.mark.parametrize("limit,pieces", [(1 << 30, 1), (4000, 2)])
def test_a_checkpoints_rows_come_whole_or_in_pieces_to_one_table(
        monkeypatch, limit, pieces):
    """`_extract_file_actions` at a test's size: under the limit the one
    `combine_chunks` it always made, past it (patched down) a piece at a
    time, to the same rows."""
    n = 90
    stats = pa.array(['{"numRecords":10,"minValues":{"x":%d}}' % i
                      if i % 7 else None for i in range(n)], pa.string())
    rows = pa.StructArray.from_arrays(
        [pa.array([deltalog.path_of(i) for i in range(n)]),
         pa.array(np.full(n, 7, np.int64)), stats],
        names=["path", "size", "stats"])
    add = pa.chunked_array([pa.nulls(2, rows.type), rows.slice(0, 40),
                            rows.slice(40)])
    monkeypatch.setattr(columnar, "_COMBINE_WHOLE_BYTES", limit)
    got = columnar._extract_file_actions(
        pa.table({"add": add}), "add", np.arange(n + 2, dtype=np.int64),
        np.arange(n + 2, dtype=np.int32))
    # in pieces the wide column keeps the file's chunks (here none is
    # past 1 GiB: every column is made one chunk again)
    assert got.column("path").num_chunks == 1
    assert got.column("path").to_pylist() == [deltalog.path_of(i)
                                              for i in range(n)]
    assert got.column("version").to_pylist() == list(range(2, n + 2))
    assert got.column("stats").to_pylist() == stats.to_pylist()
    assert got.schema == columnar.CANONICAL_FILE_ACTION_SCHEMA


def test_a_narrow_tables_probe_gathers_too(tmp_path, monkeypatch):
    """One way for every table: the candidates' keys are gathered out
    of the held rows as they lie, whatever the table's width and
    however many chunks the landings have left it in (`Table.take`
    would copy the whole held table first)."""
    m = deltastream.generate(str(tmp_path), dict(PARAMS, commits=64), seed=6)
    table = Table.for_path(m.table_path)
    assert table.latest_snapshot().num_files == m.num_files()   # a state
    gathered = []
    gather = state_mod.gather_rows
    monkeypatch.setattr(state_mod, "gather_rows", lambda held, rows: (
        gathered.append((held.column_names, held.column(0).num_chunks))
        or gather(held, rows)))
    seen = []
    obs.set_trace_mode("verbose")
    try:
        for _ in range(3):
            obs.reset_trace_buffer()
            m.land(1)
            snapshot = table.update()
            probe = [s.to_dict()["attrs"] for s in obs.get_finished_spans()
                     if s.name == "advance.probe"]
            seen.append((probe[0]["candidates"], probe[0]["cleared"]))
            assert sorted(snapshot.state.add_files_table.column(
                "path").to_pylist()) == [deltalog.path_of(int(i))
                                         for i in m.live_ids()]
    finally:
        obs.set_trace_mode("off")
    assert seen == [(20, 20)] * 3
    probes = [g for g in gathered if g[0] == ["path", "dv_id"]]
    chunks = [n for _, n in probes]     # one more chunk a landing
    assert chunks == list(range(chunks[0], chunks[0] + 3))


@pytest.mark.parametrize("wide_chunks,narrow_chunks", [(1, 1), (7, 7), (7, 1),
                                                       (20, 2), (3, 5)])
def test_rows_are_gathered_alike_however_the_columns_are_chunked(
        wide_chunks, narrow_chunks):
    """`gather_rows` over a table whose wide column lies in other
    chunks than the rest: the rows `combine_chunks().take` gives."""
    n = 1000
    whole = pa.table({"path": [deltalog.path_of(i) for i in range(n)],
                      "size": np.arange(n),
                      "stats": [f'{{"numRecords":{i}}}' if i % 5 else None
                                for i in range(n)]})

    def in_chunks(col, k):
        step = -(-n // k)
        return pa.chunked_array([col.chunk(0).slice(lo, step)
                                 for lo in range(0, n, step)])

    table = pa.Table.from_arrays(
        [in_chunks(whole.column("path"), narrow_chunks),
         in_chunks(whole.column("size"), narrow_chunks),
         in_chunks(whole.column("stats"), wide_chunks)], schema=whole.schema)
    rng = np.random.default_rng(wide_chunks)
    for rows in (np.sort(rng.choice(n, 60, replace=False)),
                 np.arange(140, 420), np.array([0, n - 1]),
                 np.zeros(0, np.int64)):
        got = state_mod.gather_rows(table, rows)
        assert got.schema == whole.schema
        assert got.combine_chunks().equals(whole.take(pa.array(rows)))
