"""The plain reference of the cell `tpcds-q-mix`: the standard
library's `sqlite3` over the generator's Arrow tables.

Of each table the columns the queries name are loaded, in bulk and by
column; a dimension's surrogate key is the table's `INTEGER PRIMARY
KEY`, which is an index on it and fair. A `decimal(p,2)` column is
loaded as its whole number of cents, so that a `sum` over it is exact
integer arithmetic; a query's text is run as it was written, less its
`LIMIT`, and what it returns is turned back by the kind of each output
column (`tpcds_queries.QUERIES[name].kinds`): a `cents` column stays the
integer of cents it is, an `avg_cents` column is divided by 100 into the
units the query speaks of.

It is the owner of the semantics: where it and the engine differ, the
engine is wrong until shown otherwise. SQLite orders NULL first
ascending and last descending, as Spark does; it compares text by its
octets, as the engine compares strings; a NULL key joins nothing and an
aggregate skips NULLs.

Nothing of `delta_tpu`, `chipbench`, `tests`, pandas or numpy is
imported: `sqlite3`, `re`, `time` and pyarrow, which the tables are made
of (and whose arrays hand their whole numbers over as a list).
"""

from __future__ import annotations

import re
import sqlite3
import time

import pyarrow as pa
import pyarrow.compute as pc

WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")
LIMIT = re.compile(r"\blimit\s+\d+\s*$", re.IGNORECASE)


def named_columns(tables: dict, texts) -> dict:
    """Per table, its columns that some query's text names, in the
    table's order. TPC-DS prefixes every column with its table's
    initials, so a word of a text that is a column's name is that
    column."""
    words = set()
    for text in texts:
        words.update(w.lower() for w in WORD.findall(text))
    return {name: [c for c in table.column_names if c.lower() in words]
            for name, table in tables.items()}


def as_sqlite(column: pa.ChunkedArray):
    """(declared type, Python values): a decimal as its integer of
    cents, anything else as it is."""
    kind = column.type
    if pa.types.is_decimal(kind):
        if kind.scale != 2:
            raise ValueError(f"a money column of scale {kind.scale}")
        column = pc.cast(pc.multiply(column, pa.scalar(100)), pa.int64())
    elif pa.types.is_string(kind):
        return "TEXT", column.to_pylist()
    elif not pa.types.is_integer(kind):
        raise ValueError(f"no SQLite column for {kind}")
    # whole numbers by the buffer (ten times `to_pylist`'s pace over
    # millions of rows), the nulls put back where they were
    values = pc.fill_null(column, 0).to_numpy().tolist()
    if column.null_count:
        for i in pc.indices_nonzero(pc.is_null(column)).to_pylist():
            values[i] = None
    return "INTEGER", values


class Oracle:
    def __init__(self, tables: dict, texts):
        """Load the named columns of `tables` (name -> Arrow table)."""
        started = time.perf_counter()
        self.db = sqlite3.connect(":memory:")
        self.loaded = {}
        for name, columns in named_columns(tables, texts).items():
            if not columns:
                continue
            table = tables[name]
            kinds, values = zip(*(as_sqlite(table.column(c))
                                  for c in columns))
            key = table.column_names[0]     # a dimension's surrogate key
            unique = (name != "store_sales" and key in columns)
            declared = ", ".join(
                f"{c} {k}" + (" PRIMARY KEY" if unique and c == key else "")
                for c, k in zip(columns, kinds))
            self.db.execute(f"CREATE TABLE {name} ({declared})")
            marks = ", ".join("?" * len(columns))
            self.db.executemany(f"INSERT INTO {name} VALUES ({marks})",
                                zip(*values))
            self.loaded[name] = (table.num_rows, columns)
        self.db.execute("ANALYZE")
        self.db.commit()
        self.load_s = time.perf_counter() - started

    def answer(self, text: str, kinds) -> list:
        """Every row the query returns without its `LIMIT`, in its
        `ORDER BY`'s order, as tuples."""
        rows = self.db.execute(LIMIT.sub("", text.strip())).fetchall()
        return [tuple(v / 100.0 if k == "avg_cents" and v is not None else v
                      for v, k in zip(row, kinds)) for row in rows]
