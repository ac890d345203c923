"""Independent Iceberg metadata reader — the UniForm conformance
oracle (VERDICT r3 ask #6).

Reconstructs a converted table's live data-file set purely from the
Iceberg spec: version-hint → vN.metadata.json → current snapshot →
manifest-list (Avro OCF) → manifests (Avro OCF) → data-file entries
with ADDED/EXISTING status. Shares ZERO code with
`delta_tpu.interop` — including Avro: the object-container-file
decoder below is written from the Avro 1.11 specification
(https://avro.apache.org/docs/1.11.1/specification/), the same way
`chipbench/reference/oracle.py` re-reads the Delta log from
PROTOCOL.md.

Reference counterpart: real Iceberg libraries reading UniForm output
(`IcebergConversionTransaction.scala:1` writes through the actual
Iceberg SDK; pyiceberg is not in this environment, so the spec itself
is the arbiter).
"""

from __future__ import annotations

import json
import os
import struct

# --------------------------------------------------- Avro (from spec)

_MAGIC = b"Obj\x01"


class _Reader:
    def __init__(self, data: bytes):
        self.data = data
        self.pos = 0

    def read(self, n: int) -> bytes:
        if self.pos + n > len(self.data):
            raise EOFError("truncated avro data")
        out = self.data[self.pos:self.pos + n]
        self.pos += n
        return out

    def at_end(self) -> bool:
        return self.pos >= len(self.data)

    # spec: ints/longs are zig-zag encoded variable-length integers
    def varint(self) -> int:
        shift = 0
        acc = 0
        while True:
            b = self.read(1)[0]
            acc |= (b & 0x7F) << shift
            if not b & 0x80:
                break
            shift += 7
        return (acc >> 1) ^ -(acc & 1)

    def bytes_(self) -> bytes:
        return self.read(self.varint())

    def string(self) -> str:
        return self.bytes_().decode("utf-8")


def _decode(r: _Reader, schema):
    """Decode one value of `schema` (the spec's per-type encodings for
    the subset Iceberg metadata uses)."""
    if isinstance(schema, str):
        t = schema
    elif isinstance(schema, list):  # union: varint branch index
        branch = r.varint()
        return _decode(r, schema[branch])
    elif isinstance(schema, dict):
        t = schema["type"]
    else:
        raise ValueError(f"bad schema node {schema!r}")

    if t == "null":
        return None
    if t == "boolean":
        return r.read(1) != b"\x00"
    if t in ("int", "long"):
        return r.varint()
    if t == "float":
        return struct.unpack("<f", r.read(4))[0]
    if t == "double":
        return struct.unpack("<d", r.read(8))[0]
    if t == "bytes":
        return r.bytes_()
    if t == "string":
        return r.string()
    if t == "record":
        return {f["name"]: _decode(r, f["type"])
                for f in schema["fields"]}
    if t == "array":
        out = []
        while True:
            n = r.varint()
            if n == 0:
                break
            if n < 0:  # negative count: block byte size follows
                r.varint()
                n = -n
            for _ in range(n):
                out.append(_decode(r, schema["items"]))
        return out
    if t == "map":
        out = {}
        while True:
            n = r.varint()
            if n == 0:
                break
            if n < 0:
                r.varint()
                n = -n
            for _ in range(n):
                out[r.string()] = _decode(r, schema["values"])
        return out
    if t == "fixed":
        return r.read(schema["size"])
    raise ValueError(f"unsupported avro type {t!r}")


def read_avro_file(path: str):
    """Spec decoder for an Avro object container file; returns
    (records, header_meta)."""
    with open(path, "rb") as f:
        r = _Reader(f.read())
    if r.read(4) != _MAGIC:
        raise ValueError(f"{path}: not an Avro object container file")
    meta = {}
    while True:
        n = r.varint()
        if n == 0:
            break
        if n < 0:
            r.varint()
            n = -n
        for _ in range(n):
            k = r.string()
            meta[k] = r.bytes_()
    codec = meta.get("avro.codec", b"null")
    if codec not in (b"null",):
        raise ValueError(f"unsupported codec {codec!r}")
    schema = json.loads(meta["avro.schema"])
    sync = r.read(16)
    records = []
    while not r.at_end():
        count = r.varint()
        size = r.varint()
        block = _Reader(r.read(size))
        for _ in range(count):
            records.append(_decode(block, schema))
        if r.read(16) != sync:
            raise ValueError(f"{path}: sync marker mismatch")
    return records, meta


# ---------------------------------------------- Iceberg (from spec)

_STATUS_DELETED = 2


def current_metadata(table_path: str) -> dict:
    meta_dir = os.path.join(table_path, "metadata")
    with open(os.path.join(meta_dir, "version-hint.text")) as f:
        v = int(f.read().strip())
    with open(os.path.join(meta_dir, f"v{v}.metadata.json")) as f:
        return json.load(f)


def live_data_files(table_path: str) -> set:
    """The queryable file set per the Iceberg spec: walk the CURRENT
    snapshot's manifest list; within each data manifest keep entries
    whose status is ADDED(1) or EXISTING(0); DELETED(2) entries exist
    only for incremental consumers."""
    md = current_metadata(table_path)
    snap_id = md["current-snapshot-id"]
    if snap_id in (None, -1):
        return set()
    snap = next(s for s in md["snapshots"]
                if s["snapshot-id"] == snap_id)
    manifests, _ = read_avro_file(snap["manifest-list"])
    live = set()
    for m in manifests:
        entries, _ = read_avro_file(m["manifest_path"])
        for e in entries:
            if e["status"] == _STATUS_DELETED:
                continue
            live.add(e["data_file"]["file_path"])
    return live


def snapshot_lineage(table_path: str) -> list:
    """snapshot-ids in log order (metadata.json snapshot-log)."""
    md = current_metadata(table_path)
    return [s["snapshot-id"] for s in md.get("snapshot-log", [])]


def total_record_count(table_path: str) -> int:
    """Sum of record_count over live entries (cross-check against the
    Delta side's numRecords stats)."""
    md = current_metadata(table_path)
    snap_id = md["current-snapshot-id"]
    if snap_id in (None, -1):
        return 0
    snap = next(s for s in md["snapshots"]
                if s["snapshot-id"] == snap_id)
    manifests, _ = read_avro_file(snap["manifest-list"])
    total = 0
    for m in manifests:
        entries, _ = read_avro_file(m["manifest_path"])
        for e in entries:
            if e["status"] != _STATUS_DELETED:
                total += e["data_file"]["record_count"]
    return total
