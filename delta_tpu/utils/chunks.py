"""Arrow columns a piece at a time. An Arrow `string` column's offsets
are 32-bit a chunk, so a column past 2 GiB (the stats strings of a fact
table's 2.4M files) can be held only as chunks, and nothing that
concatenates it (`combine_chunks`, `Table.take`, `concat_arrays`) can
run over it whole."""

from __future__ import annotations

from typing import Iterator, Union

import pyarrow as pa


def pieces(col: Union[pa.Array, pa.ChunkedArray], limit: int,
           join: bool = True) -> Iterator[pa.Array]:
    """`col` as arrays of at most `limit` bytes each (a single value
    may pass it), in order: chunks joined while they fit (a copy of
    them; with `join` false each chunk is a piece of its own, as it
    lies), a chunk past the limit cut into slices of rows."""
    group, size = [], 0
    for chunk in (col.chunks if isinstance(col, pa.ChunkedArray) else [col]):
        step = max(1, len(chunk) * limit // max(chunk.nbytes, 1)) \
            if chunk.nbytes > limit else max(len(chunk), 1)
        for lo in range(0, len(chunk), step):
            part = chunk.slice(lo, step)
            if group and (not join or size + part.nbytes > limit):
                yield pa.concat_arrays(group) if len(group) > 1 else group[0]
                group, size = [], 0
            group.append(part)
            size += part.nbytes
    if group:
        yield pa.concat_arrays(group) if len(group) > 1 else group[0]
