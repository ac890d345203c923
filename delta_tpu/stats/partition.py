"""Partition-value handling.

Partition values are serialized as strings in `add.partitionValues`
(PROTOCOL.md Partition Value Serialization): `null` for NULL, ISO dates,
plain decimal numbers, etc. This module reconstructs typed columns from
the string map for partition pruning, and serializes values on write.
"""

from __future__ import annotations

import datetime as dt
from typing import Dict, List, Optional, Sequence

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from delta_tpu.models.schema import (
    PrimitiveType,
    StructType,
    to_arrow_type,
)


def serialize_partition_value(value) -> Optional[str]:
    """Python value → partition-value string (None stays None = null)."""
    if value is None:
        return None
    if isinstance(value, bool):
        return "true" if value else "false"
    if isinstance(value, (dt.datetime,)):
        return value.strftime("%Y-%m-%d %H:%M:%S.%f")
    if isinstance(value, dt.date):
        return value.isoformat()
    if isinstance(value, bytes):
        return value.decode("utf-8", errors="replace")
    return str(value)


def deserialize_partition_value(s: Optional[str], dtype: PrimitiveType):
    if s is None:
        return None
    name = dtype.name
    if name == "string":
        return s
    if name in ("long", "integer", "short", "byte"):
        return int(s)
    if name in ("double", "float"):
        return float(s)
    if name == "boolean":
        return s.lower() == "true"
    if name == "date":
        return dt.date.fromisoformat(s)
    if name in ("timestamp", "timestamp_ntz"):
        try:
            return dt.datetime.fromisoformat(s)
        except ValueError:
            return dt.datetime.strptime(s, "%Y-%m-%d %H:%M:%S.%f")
    if dtype.is_decimal:
        import decimal

        return decimal.Decimal(s)
    return s


def _partition_field_types(metadata) -> Dict[str, tuple]:
    """logical name -> (map key in partitionValues, type). Under column
    mapping the map is keyed by physical names."""
    out: Dict[str, tuple] = {}
    schema = metadata.schema if metadata is not None else None
    mapped = (
        metadata is not None
        and metadata.configuration.get("delta.columnMapping.mode", "none") != "none"
    )
    for c in (metadata.partitionColumns if metadata else []):
        dtype = PrimitiveType("string")
        key = c
        if schema is not None and c in schema:
            f = schema[c]
            if isinstance(f.dataType, PrimitiveType):
                dtype = f.dataType
            if mapped:
                key = f.physical_name
        out[c] = (key, dtype)
    return out


def partition_values_to_columns(pv_column: pa.ChunkedArray, metadata) -> pa.Table:
    """Explode the partitionValues map column into typed columns named
    after the partition columns. Vectorized: map keys/items flattened once."""
    types = _partition_field_types(metadata)
    if not types:
        return pa.table({})
    arr = (
        pv_column.combine_chunks()
        if isinstance(pv_column, pa.ChunkedArray)
        else pv_column
    )
    n = len(arr)
    # Flatten map → per-row dict lookup via numpy. Maps are small (few
    # partition columns), so flatten + searchsorted-style grouping:
    offsets = np.asarray(arr.offsets)
    first, end = (int(offsets[0]), int(offsets[-1])) if n else (0, 0)
    # a slice keeps its parent's entries: those of its own rows
    keys = np.asarray(arr.keys[first:end], dtype=object)
    items = np.asarray(arr.items[first:end], dtype=object)
    row_of_entry = np.repeat(np.arange(n), np.diff(offsets))

    cols = {}
    for name, (map_key, dtype) in types.items():
        values = np.full(n, None, dtype=object)
        # a writer that keyed a mapped column by its logical name is
        # read too; the physical name's entry wins
        for key in dict.fromkeys((name, map_key)):
            sel = keys == key
            values[row_of_entry[sel]] = items[sel]
        py = [deserialize_partition_value(v, dtype) for v in values]
        try:
            cols[name] = pa.array(py, to_arrow_type(dtype))
        except (pa.ArrowInvalid, pa.ArrowTypeError):
            cols[name] = pa.array([None if v is None else str(v) for v in values])
    return pa.table(cols)


def partition_values_to_batch(
    pv_dicts: Sequence[Dict[str, Optional[str]]], partition_columns: List[str]
) -> pa.Table:
    """Small-scale helper (conflict checking): list of string maps → typed-ish
    batch (strings; callers' literals compare as strings)."""
    cols = {}
    for c in partition_columns:
        cols[c] = pa.array([d.get(c) for d in pv_dicts], pa.string())
    return pa.table(cols) if cols else pa.table({})


def partition_path(partition_values: Dict[str, Optional[str]], partition_columns: List[str]) -> str:
    """Hive-style directory fragment `col1=v1/col2=v2/` (empty for
    unpartitioned). `__HIVE_DEFAULT_PARTITION__` encodes null."""
    from urllib.parse import quote

    parts = []
    for c in partition_columns:
        v = partition_values.get(c)
        ev = "__HIVE_DEFAULT_PARTITION__" if v is None else quote(v, safe="")
        parts.append(f"{c}={ev}")
    return "/".join(parts) + ("/" if parts else "")
