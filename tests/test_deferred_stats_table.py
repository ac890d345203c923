"""The parsed stats table, derived and not carried (PR 36).

A refresh brings the lanes forward; the parsed Arrow table, which only
the fallback ladder reads, goes on as pieces and the numbers of the rows
that went (`stats/skipping.py::ParsedPieces`) and is made into one table
when a reader first asks for a leaf. Held here: the table, once asked
for, is `build_index`'s over the live files, row for row and schema for
schema, after any number of appends; a conjunct only the ladder takes
keeps the same files on a carried index as on one built in full; the
two counters say when a table was made and when it was handed on; what
is carried stays bounded; and a ladder plan racing the hand-over never
fails."""

import datetime as dt
import json
import os
import sys
import threading

import numpy as np
import pyarrow as pa
import pytest

from delta_tpu import obs
from delta_tpu.engine.host import HostEngine
from delta_tpu.expressions import col, lit
from delta_tpu.replay import state as state_mod
from delta_tpu.stats import skipping
from delta_tpu.stats.device_index import append_index, build_index
from delta_tpu.stats.skipping import ParsedPieces
from delta_tpu.table import Table

T0 = dt.datetime(2024, 1, 1, tzinfo=dt.timezone.utc)
SCHEMAS = {
    "one-column": (("x", "long"),),
    # NEXmark's bid, as `nexmark-bids-4m-stream` declares it
    "bids": (("auction", "long"), ("bidder", "long"), ("price", "long"),
             ("channel", "string"), ("url", "string"),
             ("dateTime", "timestamp"), ("extra", "string")),
    "money": (("id", "long"), ("amount", "decimal(10,2)")),
}
CHANNELS = 7


def stamp(seconds):
    return (T0 + dt.timedelta(seconds=seconds)).strftime(
        "%Y-%m-%dT%H:%M:%S.") + "000Z"


def ends(fid):
    """(least, most) of every column any of the schemas has, file `fid`."""
    return ({"x": fid * 10, "id": fid * 10, "auction": fid * 10,
             "bidder": 7 * fid, "price": 100 + fid % 13,
             "channel": "apple", "url": f"https://bids.example/a{fid:05d}",
             "dateTime": stamp(fid), "extra": f"a{fid % 5}",
             "amount": fid + 0.25},
            {"x": fid * 10 + 9, "id": fid * 10 + 9, "auction": fid * 10 + 9,
             "bidder": 7 * fid + 6, "price": 900 + fid % 17,
             "channel": f"channel-{fid % CHANNELS}",
             "url": f"https://bids.example/z{fid:05d}",
             "dateTime": stamp(fid + 9), "extra": f"z{fid % 5}",
             "amount": fid + 8.75})


def file_stats(schema, fid):
    names = [name for name, _ in SCHEMAS[schema]]
    least, most = ends(fid)
    return json.dumps({"numRecords": 10,
                       "minValues": {k: least[k] for k in names},
                       "maxValues": {k: most[k] for k in names},
                       "nullCount": {k: fid % 3 for k in names}},
                      separators=(",", ":"))


class Log:
    """A table of one of `SCHEMAS`, written commit by commit as raw JSON;
    it knows which file ids are live."""

    def __init__(self, root, schema, n_base):
        self.root, self.schema = str(root), schema
        self.log = os.path.join(self.root, "_delta_log")
        os.makedirs(self.log)
        self.version, self.live = -1, []
        fields = [{"name": n, "type": t, "nullable": True, "metadata": {}}
                  for n, t in SCHEMAS[schema]]
        self.write([
            {"protocol": {"minReaderVersion": 1, "minWriterVersion": 2}},
            {"metaData": {
                "id": "deferred-" + schema,
                "format": {"provider": "parquet", "options": {}},
                "schemaString": json.dumps({"type": "struct",
                                            "fields": fields}),
                "partitionColumns": [], "configuration": {}}}])
        self.next_fid = 0
        self.commit(adds=n_base)

    @staticmethod
    def path(fid):
        return f"part-{fid:05d}.parquet"

    def write(self, actions):
        self.version += 1
        with open(os.path.join(self.log, f"{self.version:020d}.json"),
                  "w") as f:
            f.writelines(json.dumps(a) + "\n" for a in actions)

    def commit(self, adds=0, removes=()):
        """Land `adds` new files and take the live files numbered
        `removes` (positions among the live ones) away."""
        gone = [self.live[i] for i in removes]
        new = list(range(self.next_fid, self.next_fid + adds))
        self.next_fid += adds
        self.write(
            [{"remove": {"path": self.path(fid), "dataChange": True,
                         "deletionTimestamp": self.version}} for fid in gone]
            + [{"add": {"path": self.path(fid), "partitionValues": {},
                        "size": 100, "modificationTime": self.version,
                        "dataChange": True,
                        "stats": file_stats(self.schema, fid)}}
               for fid in new])
        self.live = [fid for fid in self.live if fid not in gone] + new


LANES = {       # a filter every conjunct of which compiles to the lanes
    "one-column": (col("x") >= lit(100)) & (col("x") < lit(400)),
    "bids": (col("auction") >= lit(100)) & (col("auction") < lit(400)),
    "money": (col("id") >= lit(100)) & (col("id") < lit(400)),
}
LADDER = {      # (schema, a filter with a conjunct only the ladder takes,
                #  which of the live file ids it keeps)
    "channel-equals": (
        "bids", col("channel") == lit("channel-3"),
        lambda fid: f"channel-{fid % CHANNELS}" >= "channel-3"),
    "channel-and-a-lane": (
        "bids", (col("channel") >= lit("channel-5")) & (col("auction") < lit(9000)),
        lambda fid: fid % CHANNELS >= 5 and fid * 10 < 9000),
    "url-prefix": (
        "bids", col("url") < lit("https://bids.example/a00500"),
        lambda fid: fid < 500),
    "a-decimal": (
        "money", col("amount") < lit(700.5),
        lambda fid: fid + 0.25 < 700.5),
    "an-inexact-literal": (
        "one-column", col("x") < lit(4000.5),
        lambda fid: fid * 10 < 4000.5),
}

TABLE_BUILDS = obs.counter("scan.stats_index_table_builds")
TABLE_DEFERRED = obs.counter("scan.stats_index_table_deferred")
APPENDS = obs.counter("scan.stats_index_appends")
INDEX_BUILDS = obs.counter("scan.stats_index_builds")


class Counted:
    """How far the four counters moved since this was made."""

    def __init__(self):
        self.at = self.now()

    @staticmethod
    def now():
        return [c.value for c in (INDEX_BUILDS, APPENDS, TABLE_DEFERRED,
                                  TABLE_BUILDS)]

    def moved(self):
        return tuple(b - a for a, b in zip(self.at, self.now()))


def plan(snapshot, pred):
    return sorted(snapshot.scan(filter=pred).file_paths())


def refresh(log, snapshot, k, dropped, pred=None):
    """`k` commits of three files each, `dropped` live files gone with
    each, the held snapshot brought over each and `pred` planned on it
    (no plan where it is None)."""
    for i in range(k):
        removes = [(11 * i + 5 * j) % len(log.live) for j in range(dropped)]
        log.commit(adds=3, removes=sorted(set(removes)))
        snapshot = snapshot.update()
        if pred is not None:
            plan(snapshot, pred)
    return snapshot


def is_deferred(index):
    return isinstance(index.arrow_index._rows, ParsedPieces)


def assert_as_built_in_full(snapshot):
    """The held index against one built from every live file's stats
    string: the schema before any table is made, then lanes, kinds and
    the table asked for."""
    idx = snapshot.state.stats_index
    ref = build_index(snapshot.state.add_files_table,
                      metadata=snapshot.metadata)
    assert idx.arrow_index.schema.equals(ref.arrow_index.schema)
    assert idx.n == ref.n and idx.cols == ref.cols
    assert idx.unindexed == ref.unindexed
    assert np.array_equal(idx.vals, ref.vals)
    assert np.array_equal(idx.valid, ref.valid)
    table = idx.arrow_index._table
    assert table.equals(ref.arrow_index._table)
    assert table.schema.equals(ref.arrow_index._table.schema)
    assert all(column.num_chunks == 1 for column in table.columns)


# ---- the table, once asked for, is the one a full build gives ----

@pytest.mark.parametrize("schema", ["one-column", "bids"])
@pytest.mark.parametrize("dropped", [0, 2], ids=["none-dropped", "dropped"])
@pytest.mark.parametrize("k", [1, 5, 40])
def test_the_table_asked_for_after_k_appends_is_a_full_builds(
        tmp_path, k, dropped, schema):
    log = Log(tmp_path, schema, n_base=1000)
    snapshot = Table.for_path(log.root).latest_snapshot()
    plan(snapshot, LANES[schema])
    assert not is_deferred(snapshot.state.stats_index)
    counted = Counted()
    snapshot = refresh(log, snapshot, k, dropped, LANES[schema])
    # k refreshes, each an append that handed the rows on; no table made
    assert counted.moved() == (0, k, k, 0)
    idx = snapshot.state.stats_index
    assert is_deferred(idx)
    pieces = idx.arrow_index.carried()
    assert pieces.table.num_rows == 1000 + 3 * k
    assert len(pieces.dead) == 1000 + 3 * k - len(log.live)
    assert_as_built_in_full(snapshot)
    assert counted.moved() == (0, k, k, 1) and not is_deferred(idx)


def test_a_seed_passed_on_over_advances_with_no_plan_between(tmp_path):
    log = Log(tmp_path, "bids", n_base=300)
    snapshot = Table.for_path(log.root).latest_snapshot()
    plan(snapshot, LANES["bids"])
    counted = Counted()
    snapshot = refresh(log, snapshot, 4, dropped=3)
    assert snapshot.state.stats_index is None
    assert snapshot.state.stats_index_seed.parsed.table.num_rows == 300
    plan(snapshot, LANES["bids"])
    assert counted.moved() == (0, 1, 1, 0)
    assert_as_built_in_full(snapshot)


# ---- a conjunct only the ladder takes ----

@pytest.mark.parametrize("every", [False, True],
                         ids=["at-the-end", "at-every-refresh"])
@pytest.mark.parametrize("case", sorted(LADDER))
def test_a_ladder_conjunct_keeps_what_it_keeps_on_a_full_build(
        tmp_path, case, every):
    schema, pred, keeps = LADDER[case]
    log = Log(tmp_path, schema, n_base=1000)
    table = Table.for_path(log.root)
    snapshot = table.latest_snapshot()
    plan(snapshot, pred if every else LANES[schema])
    counted = Counted()
    snapshot = refresh(log, snapshot, 5, dropped=4,
                       pred=pred if every else LANES[schema])
    got = plan(snapshot, pred)
    # a reader whose every plan walks the ladder makes the table once a
    # refresh, as every refresh did before; another one, once
    assert counted.moved() == (0, 5, 5, 5 if every else 1)
    want = sorted(Log.path(fid) for fid in log.live if keeps(fid))
    assert 0 < len(want) < len(log.live)
    assert got == want
    fresh = Table.for_path(log.root).latest_snapshot()
    assert plan(fresh, pred) == want and not is_deferred(
        fresh.state.stats_index)
    assert plan(Table.for_path(log.root, HostEngine()).latest_snapshot(),
                pred) == want
    assert_as_built_in_full(snapshot)


# ---- the counters ----

def test_the_table_is_made_once_a_version_however_many_plans_follow(
        tmp_path):
    _, ladder, _ = LADDER["channel-equals"]
    log = Log(tmp_path, "bids", n_base=500)
    snapshot = Table.for_path(log.root).latest_snapshot()
    plan(snapshot, LANES["bids"])
    counted = Counted()
    for k in range(1, 4):       # refreshes whose plans compile to lanes
        snapshot = refresh(log, snapshot, 1, 2, LANES["bids"])
        plan(snapshot, LANES["bids"])
        assert counted.moved() == (0, k, k, 0)
    first = plan(snapshot, ladder)
    assert counted.moved() == (0, 3, 3, 1)
    for pred in (ladder, LANES["bids"], ladder,
                 LADDER["url-prefix"][1], ladder):
        plan(snapshot, pred)
    assert plan(snapshot, ladder) == first
    assert counted.moved() == (0, 3, 3, 1)
    # the next version starts from the table made: two pieces
    snapshot = refresh(log, snapshot, 1, 2, LANES["bids"])
    pieces = snapshot.state.stats_index.arrow_index.carried()
    assert pieces.table.column(0).num_chunks == 2 and len(pieces.dead) == 2
    plan(snapshot, ladder)
    plan(snapshot, ladder)
    assert counted.moved() == (0, 4, 4, 2)


def test_a_column_the_index_lacks_makes_no_table(tmp_path):
    """The schema says a leaf is not there: the conjunct keeps every
    file, as before, and nothing is combined to find that out."""
    log = Log(tmp_path, "one-column", n_base=200)
    snapshot = Table.for_path(log.root).latest_snapshot()
    plan(snapshot, LANES["one-column"])
    snapshot = refresh(log, snapshot, 2, 1, LANES["one-column"])
    counted = Counted()
    idx = snapshot.state.stats_index
    assert idx.arrow_index.min_values(("y",)) is None
    assert idx.arrow_index.null_count(("x", "z")) is None
    assert idx.arrow_index._leaf("tightBounds", ("x",)) is None
    assert counted.moved() == (0, 0, 0, 0) and is_deferred(idx)
    assert idx.arrow_index.num_records().to_pylist() == [10] * len(log.live)
    assert counted.moved() == (0, 0, 0, 1)


# ---- what is carried stays bounded ----

def pieces_as_bools(pieces):
    keep = np.ones(pieces.table.num_rows, bool)
    keep[pieces.dead] = False
    return keep


@pytest.mark.parametrize("seed", range(6))
def test_the_rows_that_went_are_numbered_among_the_pieces(seed):
    """`ParsedPieces.advanced` against a selection kept as bools: ranks
    among the live rows become positions among all of them."""
    rng = np.random.default_rng(seed)
    schema = pa.schema([("numRecords", pa.int64())])

    def rows(lo, hi):
        return pa.table({"numRecords": np.arange(lo, hi)}, schema=schema)

    pieces, keep, landed = ParsedPieces(rows(0, 900), np.zeros(0, np.int64)), \
        np.ones(900, bool), 900
    for _ in range(12):
        live = np.flatnonzero(keep)
        ranks = np.sort(rng.choice(len(live), int(rng.integers(0, 9)),
                                   replace=False))
        tail = int(rng.integers(0, 6))
        keep[live[ranks]] = False
        keep = np.concatenate([keep, np.ones(tail, bool)])
        pieces = pieces.advanced(ranks, rows(landed, landed + tail))
        landed += tail
        assert isinstance(pieces, ParsedPieces)
        assert pieces.dead.dtype == np.int64
        assert np.array_equal(pieces_as_bools(pieces), keep)
    want = np.flatnonzero(keep)
    assert pieces.combined().column("numRecords").to_pylist() == want.tolist()


def test_pieces_and_dead_rows_stay_under_their_bounds_over_500_appends():
    """Three rows gone and four landed at every two appends: the small
    pieces are merged once there are more than `_MAX_SMALL_CHUNKS`, the
    rows that went are dropped once they pass an eighth of the rows
    carried, and neither more than a few times in 500 appends."""
    schema = "bids"
    stats = [file_stats(schema, fid) for fid in range(1600)]
    live = np.ones(1600, bool)

    def files():
        return pa.table({"stats": pa.array(
            [s for s, alive in zip(stats, live) if alive], pa.string())})

    idx = build_index(files())
    counted = Counted()
    most_pieces = most_dead = merges = chunks = 0
    rng = np.random.default_rng(36)
    for k in range(500):
        seed = idx.seed(live)
        gone = rng.choice(np.flatnonzero(live), 1 + k % 2, replace=False)
        new = [file_stats(schema, len(stats) + j) for j in range(2)]
        stats += new
        live = np.concatenate([live, np.ones(2, bool)])
        live[gone] = False
        idx, attrs = append_index(seed, live, pa.chunked_array(
            [pa.array(new, pa.string())]))
        assert attrs == {"rows": 2, "dropped": len(gone)}
        pieces = idx.arrow_index.carried()
        was, chunks = chunks, pieces.table.column(0).num_chunks
        merges += chunks < was and len(pieces.dead) > 0
        most_pieces = max(most_pieces, chunks)
        most_dead = max(most_dead, len(pieces.dead)
                        * skipping._DEAD_ROWS_SHARE / pieces.table.num_rows)
    _, _, deferred, tables = counted.moved()
    assert most_pieces == state_mod._MAX_SMALL_CHUNKS + 1   # and the first
    assert 0.9 < most_dead <= 1.0
    assert 2 <= merges <= 8 and 2 <= tables <= 8
    assert deferred == 500 - tables
    ref = build_index(files())
    assert idx.arrow_index._table.equals(ref.arrow_index._table)
    assert np.array_equal(idx.vals, ref.vals)


# ---- a ladder plan while the index changes hands ----

def test_a_ladder_plan_races_the_hand_over_and_is_never_wrong(tmp_path):
    """Readers plan a ladder conjunct on the newest snapshot and on the
    one before while a writer lands commits and refreshes: the plan on
    the snapshot before finds its index released (its rows handed on as
    a seed, a table made of them or not) and builds again; every plan is
    the plan of its own version."""
    _, pred, keeps = LADDER["channel-and-a-lane"]
    log = Log(tmp_path, "bids", n_base=300)
    newest = [Table.for_path(log.root).latest_snapshot()]

    def wanted():
        return sorted(Log.path(fid) for fid in log.live if keeps(fid))

    want_at = {newest[0].version: wanted()}
    errors, plans, done = [], [0], threading.Event()

    def reader():
        try:
            prior = snap = newest[0]
            while not done.is_set() or plans[0] < 30:
                prior, snap = snap, newest[0]
                for s in (snap, prior):
                    assert plan(s, pred) == want_at[s.version], s.version
                    plans[0] += 1
        except Exception as e:          # raised by the main thread
            errors.append(e)

    readers = [threading.Thread(target=reader) for _ in range(4)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in readers:
            t.start()
        for k in range(16):
            log.commit(adds=3, removes=[k, len(log.live) - 1])
            want_at[log.version] = wanted()
            newest[0] = newest[0].update()
        done.set()
        for t in readers:
            t.join(timeout=120)
    finally:
        done.set()
        sys.setswitchinterval(interval)
    if errors:
        raise errors[0]
    assert not any(t.is_alive() for t in readers)
    assert plans[0] >= 30
    plan(newest[0], pred)
    assert_as_built_in_full(newest[0])
