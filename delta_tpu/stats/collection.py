"""Per-file statistics collection on write.

Reference `stats/StatisticsCollection.scala:257-356`: each written file's
AddFile carries a JSON `stats` document — `numRecords`, and
`minValues` / `maxValues` / `nullCount` per indexed leaf column (first
`delta.dataSkippingNumIndexedCols` = 32 leaves by default, or the explicit
`delta.dataSkippingStatsColumns` list).

Min/max are computed columnar (pyarrow C++ on host; numeric columns can
be reduced on-device in batch via delta_tpu.ops.stats when writing many
files in one call). String min/max are truncated to
`MAX_STRING_PREFIX_LENGTH` with the max tie-broken upward (appending
U+10FFFF would not round-trip JSON cleanly, so like the reference we
bump the last character — `StatisticsCollection.truncateMaxStringAgg`).
"""

from __future__ import annotations

import datetime as dt
import json
import math
from typing import Any, Dict, List, Optional

import pyarrow as pa
import pyarrow.compute as pc

from delta_tpu.config import (
    DATA_SKIPPING_NUM_INDEXED_COLS,
    DATA_SKIPPING_STATS_COLUMNS,
    get_table_config,
)

MAX_STRING_PREFIX_LENGTH = 32


def _truncate_min(s: str) -> str:
    return s[:MAX_STRING_PREFIX_LENGTH]


def bump_string(s: str) -> Optional[str]:
    """Smallest convenient string > every string with prefix `s`:
    increment the last bumpable character. None when all characters are
    already U+10FFFF (unbumpable -> caller drops the max stat)."""
    for i in range(len(s) - 1, -1, -1):
        if ord(s[i]) < 0x10FFFF:
            return s[:i] + chr(ord(s[i]) + 1)
    return None


def _truncate_max(s: str) -> Optional[str]:
    if len(s) <= MAX_STRING_PREFIX_LENGTH:
        return s
    # bump the truncated prefix so it >= every string it covers
    return bump_string(s[:MAX_STRING_PREFIX_LENGTH])


def _json_value(v: Any) -> Any:
    if v is None:
        return None
    if isinstance(v, float):
        if math.isnan(v):
            return "NaN"
        if math.isinf(v):
            return "Infinity" if v > 0 else "-Infinity"
        return v
    if isinstance(v, dt.datetime):
        # ISO-8601 with the offset as `+00:00`, the form upstream's
        # readers and the typed stats reader (`stats/skipping.py`) take
        # (`%z` alone gave `+0000`, which JSON inference reads as text)
        return v.isoformat(timespec="microseconds")
    if isinstance(v, dt.date):
        return v.isoformat()
    if isinstance(v, bytes):
        return v.decode("utf-8", errors="replace")
    try:
        import decimal

        if isinstance(v, decimal.Decimal):
            return float(v)
    except ImportError:
        pass
    return v


def _set_nested(d: dict, path: List[str], value) -> None:
    for p in path[:-1]:
        d = d.setdefault(p, {})
    d[path[-1]] = value


def stats_columns(schema, configuration: Dict[str, str], partition_columns: List[str]) -> List[List[str]]:
    """Leaf column name-paths to index, honoring the explicit list / first-N
    rule; partition columns are excluded (their values are in
    partitionValues)."""
    explicit = get_table_config(configuration, DATA_SKIPPING_STATS_COLUMNS)
    if explicit:
        return [c.split(".") for c in explicit]
    n = get_table_config(configuration, DATA_SKIPPING_NUM_INDEXED_COLS)
    leaves = [list(p) for p, _ in schema.leaves()]
    leaves = [p for p in leaves if p[0] not in set(partition_columns)]
    if n < 0:
        return leaves
    return leaves[:n]


def _leaf_array(table: pa.Table, path: List[str]) -> Optional[pa.ChunkedArray]:
    if path[0] not in table.column_names:
        return None
    arr = table.column(path[0])
    for p in path[1:]:
        try:
            arr = pc.struct_field(arr, p)
        except (pa.ArrowInvalid, KeyError):
            return None
    return arr


_MINMAX_TYPES = (
    pa.types.is_integer,
    pa.types.is_floating,
    pa.types.is_string,
    pa.types.is_date,
    pa.types.is_timestamp,
    pa.types.is_decimal,
)


def _supports_minmax(t: pa.DataType) -> bool:
    return any(check(t) for check in _MINMAX_TYPES)


def collect_stats(
    table: pa.Table,
    schema,
    configuration: Dict[str, str],
    partition_columns: List[str],
) -> str:
    """Stats JSON for one written file."""
    cols = stats_columns(schema, configuration, partition_columns)
    stats: dict = {"numRecords": table.num_rows}
    min_d: dict = {}
    max_d: dict = {}
    null_d: dict = {}
    for path in cols:
        arr = _leaf_array(table, path)
        if arr is None:
            continue
        null_count = arr.null_count
        _set_nested(null_d, path, int(null_count))
        if not _supports_minmax(arr.type) or arr.length() == null_count:
            continue
        is_float = pa.types.is_floating(arr.type)
        if is_float:
            # NaN must not poison min/max; delta treats NaN > any value
            no_nan = pc.drop_null(arr)
            nan_mask = pc.is_nan(no_nan)
            has_nan = pc.any(nan_mask).as_py()
            clean = no_nan.filter(pc.invert(nan_mask))
            if clean.length() == 0:
                _set_nested(min_d, path, "NaN")
                _set_nested(max_d, path, "NaN")
                continue
            mn = pc.min(clean).as_py()
            mx = pc.max(clean).as_py() if not has_nan else float("nan")
        else:
            mm = pc.min_max(arr)
            mn, mx = mm["min"].as_py(), mm["max"].as_py()
        if isinstance(mn, str):
            mn = _truncate_min(mn)
            mx_t = _truncate_max(mx)
            if mx_t is None:
                _set_nested(min_d, path, _json_value(mn))
                continue
            mx = mx_t
        _set_nested(min_d, path, _json_value(mn))
        _set_nested(max_d, path, _json_value(mx))
    if min_d:
        stats["minValues"] = min_d
        stats["maxValues"] = max_d
    stats["nullCount"] = null_d
    return json.dumps(stats, separators=(",", ":"))
