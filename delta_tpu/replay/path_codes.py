"""First-appearance dictionary codes of a string column, keyed over many
small hash tables at once.

One hash table of every path of a load (what `pd.factorize` and a plain
`dictionary_encode` build) never fits a cache at millions of distinct
strings, and one thread builds it. Here the rows are dealt to buckets
by a function of each string's own bytes, so that equal strings always
meet in one bucket; each bucket is coded by Arrow's `dictionary_encode`
on a thread of its own, its table small enough to stay in L2; and one
pass turns "the first row that carries my string" into the codes
`pd.factorize(paths, sort=False)` gives, bit for bit. No hash stands in
for a string anywhere: Arrow's tables compare the bytes, the dealing
only decides which table a string is looked up in, so a bad deal costs
time (one bucket with every row is one table again) and never the
answer.
"""

from __future__ import annotations

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from delta_tpu.utils.threads import default_scan_threads, scan_pool

# Sized on the chip's host (13 cores; PERF.md §6, PR 42). A bucket costs
# ~0.75 ms whatever it holds: its dozen calls into Arrow and numpy each
# hand the GIL on, and the hand-overs of all threads queue up. So buckets
# are few and large: 32 at 2.4M rows code in 65 ms where 128 take 158.
# Under DEAL_MIN_ROWS one table of the whole column is as fast
# (22.6 ms at 262,144 rows) as dealing it.
DEAL_MIN_ROWS = 262_144
ROWS_PER_BUCKET = 131_072   # at most; the count is the next power of two
_MAX_BUCKETS = 1 << 16  # the lane that deals is uint16 at most

_NULL_CODE = np.uint32(0xFFFFFFFF)  # pd.factorize's -1 as uint32


def bucket_count(n: int) -> int:
    """Buckets for `n` rows: a power of two near `n / ROWS_PER_BUCKET`,
    1 under `DEAL_MIN_ROWS`."""
    if n < DEAL_MIN_ROWS:
        return 1
    return min(1 << (n // ROWS_PER_BUCKET).bit_length(), _MAX_BUCKETS)


def _string_buffers(paths: pa.Array) -> tuple[np.ndarray, np.ndarray]:
    """(offsets of the `len + 1` rows of this array, overlapping
    little-endian 32-bit words of its data buffer: `words[i]` is bytes
    `i..i+3`), read in place."""
    _, offsets, data = paths.buffers()
    wide = pa.types.is_large_string(paths.type)
    offsets = np.frombuffer(offsets, dtype=np.int64 if wide else np.int32)
    offsets = offsets[paths.offset: paths.offset + len(paths) + 1]
    size = data.size if data is not None else 0
    words = np.ndarray(shape=(max(size - 3, 0),), dtype="<u4",
                       buffer=data if size else None, strides=(1,))
    return offsets, words


def _deal(offsets: np.ndarray, words: np.ndarray, lo: int, hi: int,
          buckets: int) -> tuple[np.ndarray, np.ndarray]:
    """The rows `lo..hi` dealt to `buckets`: (their row numbers, bucket by
    bucket and rising within one; the `buckets + 1` bounds of the buckets
    in that array).

    A row's bucket follows from its string's length, the four bytes round
    its middle and the four that end 22 from its end: where
    `part-<digits>.parquet` has its low digits, and where Spark's
    `part-00000-<uuid>-c000.snappy.parquet` has its uuid however long a
    partition prefix stands before it. A string under four bytes is
    dealt by its length alone."""
    start = offsets[lo:hi].astype(np.intp)
    length = offsets[lo + 1: hi + 1] - start
    h = length.astype(np.uint32) * np.uint32(0x9E3779B1)
    long_enough = length >= 4
    if long_enough.all():
        rows = slice(None)
    else:
        rows = np.flatnonzero(long_enough)
        start, length = start[rows], length[rows]
    middle = words[start + (length >> 1) - 2]
    tail = words[start + np.maximum(length - 25, 0)]
    h[rows] ^= ((middle * np.uint32(0x85EBCA77))
                ^ (tail * np.uint32(0xC2B2AE3D)))
    h *= np.uint32(0x27D4EB2F)
    h >>= np.uint32(32 - (buckets.bit_length() - 1))
    lane = h.astype(np.uint8 if buckets <= 256 else np.uint16)
    # a stable sort of a narrow lane is a counting sort
    order = np.argsort(lane, kind="stable").astype(np.uint32)
    order += np.uint32(lo)
    bounds = np.zeros(buckets + 1, dtype=np.intp)
    np.cumsum(np.bincount(lane, minlength=buckets), out=bounds[1:])
    return order, bounds


def _code_bucket(paths: pa.Array, rows: np.ndarray, first: np.ndarray,
                 is_new: np.ndarray) -> int:
    """Code one bucket's rows (rising) in a table of their own; write for
    each the row of the whole column at which its string first appears,
    and whether that is the row itself. Returns the bucket's distinct
    strings."""
    if len(rows) == 0:
        return 0
    coded = pc.dictionary_encode(paths.take(pa.array(rows)))
    local = coded.indices.to_numpy()
    first_row = np.empty(len(coded.dictionary), dtype=np.uint32)
    # rows rise, so written backwards a code keeps its first row
    first_row[local[::-1]] = rows[::-1]
    at = first_row[local]
    first[rows] = at
    is_new[rows] = at == rows
    return len(coded.dictionary)


def first_appearance_codes(paths: pa.Array) -> tuple[np.ndarray, dict]:
    """`pd.factorize(paths, sort=False)[0]` as uint32 (a null row reads
    0xFFFFFFFF, factorize's -1), and how the coding engaged: `buckets`,
    `threads`, `largest_bucket_rows`, `uniques`."""
    if not (pa.types.is_string(paths.type)
            or pa.types.is_large_string(paths.type)):
        paths = paths.cast(pa.string())
    if paths.null_count:
        valid = np.flatnonzero(
            pc.is_valid(paths).to_numpy(zero_copy_only=False))
        codes = np.full(len(paths), _NULL_CODE, dtype=np.uint32)
        codes[valid], engaged = first_appearance_codes(paths.drop_null())
        return codes, engaged

    n = len(paths)
    if n >= 1 << 32:
        raise ValueError(f"{n} rows do not fit uint32 codes")
    buckets = bucket_count(n)
    if buckets == 1:
        coded = pc.dictionary_encode(paths)
        return (coded.indices.to_numpy().astype(np.uint32),
                dict(buckets=1, threads=1, largest_bucket_rows=n,
                     uniques=len(coded.dictionary)))

    # every pass below runs slab by slab or bucket by bucket on the pool:
    # no array of the column's length is made that a thread does not fill
    pool = scan_pool()
    threads = min(default_scan_threads(), buckets)
    offsets, words = _string_buffers(paths)
    edges = np.linspace(0, n, threads + 1).astype(np.intp)
    slabs = list(zip(edges[:-1], edges[1:]))
    dealt = pool.map(lambda slab: _deal(offsets, words, *slab, buckets), slabs)

    def rows_of(b: int) -> np.ndarray:
        return np.concatenate([order[bounds[b]: bounds[b + 1]]
                               for order, bounds in dealt])

    first = np.empty(n, dtype=np.uint32)
    is_new = np.empty(n, dtype=bool)
    uniques = pool.map(
        lambda b: _code_bucket(paths, rows_of(b), first, is_new),
        range(buckets))
    rank = np.cumsum(is_new, dtype=np.uint32)
    rank -= np.uint32(1)    # row 0 is new, so nothing wraps
    codes = np.empty(n, dtype=np.uint32)

    def renumber(slab) -> None:
        lo, hi = slab
        codes[lo:hi] = rank[first[lo:hi]]

    pool.map(renumber, slabs)
    sizes = np.diff(sum(bounds for _, bounds in dealt))
    return codes, dict(buckets=buckets, threads=threads,
                       largest_bucket_rows=int(sizes.max()),
                       uniques=int(sum(uniques)))
