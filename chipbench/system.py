"""The system under test, as a user of the library drives it: the
default engine, no route variable, the public entry points. Every call
returns numpy, Arrow or Python values, so it has returned only when the
work is done."""

from __future__ import annotations

import gc


class DeltaTpu:
    def drop_caches(self) -> None:
        """A process that has never seen the table: no parsed commit
        kept, no `Table` or `Snapshot` alive."""
        from delta_tpu.replay.columnar import clear_parse_cache

        clear_parse_cache()
        gc.collect()

    def load(self, path: str):
        """Cold load: (table, snapshot)."""
        from delta_tpu import Table

        table = Table.for_path(path)
        return table, table.latest_snapshot()

    def state(self, snapshot):
        """(num_files, size_in_bytes, live path column)."""
        return (snapshot.num_files, snapshot.size_in_bytes,
                snapshot.state.add_files_table.column("path"))

    def refresh(self, table):
        return table.update()

    def plan(self, snapshot, lo: int, hi: int) -> list:
        """Paths of the files a scan of `lo <= x < hi` has to read."""
        from delta_tpu.expressions import col, lit

        pred = (col("x") >= lit(lo)) & (col("x") < lit(hi))
        return snapshot.scan(filter=pred).file_paths()
