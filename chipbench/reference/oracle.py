"""Independent Delta log reader — the conformance oracle.

A from-scratch, sequential implementation of snapshot state
reconstruction written directly from PROTOCOL.md, sharing NO code with
`delta_tpu.replay` (no columnarizer, no native scanner, no device
kernel; only stdlib json/os + pyarrow.parquet for checkpoint bytes).
Deliberately boring: per-line `json.loads`, ascending replay, last-wins
dict keyed by `(path, dvUniqueId)` — the reference's
`InMemoryLogReplay.scala:52` shape.

Purpose (VERDICT round-1 item 4): the product's two engines share one
parser, so a shared parse/semantics bug passes differential tests on
both. This oracle is the third, independent opinion: a bug in
`replay/columnar.py` or the C++ scanner now disagrees with it and gets
caught. Reference mechanism: `connectors/golden-tables/.../
GoldenTables.scala:50` (state produced by an independent writer).
"""

from __future__ import annotations

import json
import os
import re
import urllib.parse

import pyarrow.parquet as pq

_COMMIT_RE = re.compile(r"^(\d{20})\.json$")
_COMPACT_RE = re.compile(r"^(\d{20})\.(\d{20})\.compacted\.json$")
_CLASSIC_CP_RE = re.compile(r"^(\d{20})\.checkpoint\.parquet$")
_MULTI_CP_RE = re.compile(r"^(\d{20})\.checkpoint\.(\d{10})\.(\d{10})\.parquet$")
_V2_CP_RE = re.compile(r"^(\d{20})\.checkpoint\.[0-9a-zA-Z-]+\.(json|parquet)$")


def _canon_path(p: str) -> str:
    """Percent-decode relative paths the way URI-based readers do."""
    if "%" in p:
        return urllib.parse.unquote(p)
    return p


def _dv_unique_id(dv) -> str | None:
    if not dv:
        return None
    base = (dv.get("storageType") or "") + (dv.get("pathOrInlineDv") or "")
    if dv.get("offset") is not None:
        return f"{base}@{dv['offset']}"
    return base


class OracleState:
    def __init__(self):
        self.protocol = None
        self.metadata = None
        self.txns = {}
        self.domains = {}
        self.files = {}       # (path, dv_id) -> ("add"|"remove", action)
        self.latest_ict = None

    def apply(self, action: dict) -> None:
        if "protocol" in action:
            self.protocol = action["protocol"]
        elif "metaData" in action:
            self.metadata = action["metaData"]
        elif "txn" in action:
            self.txns[action["txn"]["appId"]] = action["txn"]["version"]
        elif "domainMetadata" in action:
            d = action["domainMetadata"]
            self.domains[d["domain"]] = d
        elif "add" in action:
            a = action["add"]
            key = (_canon_path(a["path"]),
                   _dv_unique_id(a.get("deletionVector")))
            self.files[key] = ("add", a)
        elif "remove" in action:
            r = action["remove"]
            key = (_canon_path(r["path"]),
                   _dv_unique_id(r.get("deletionVector")))
            self.files[key] = ("remove", r)
        elif "commitInfo" in action:
            ict = action["commitInfo"].get("inCommitTimestamp")
            if ict is not None:
                self.latest_ict = ict
        # checkpointMetadata / sidecar never participate in replay
        # (PROTOCOL.md:841)

    @property
    def live(self):
        return {k: a for k, (kind, a) in self.files.items() if kind == "add"}

    @property
    def tombstones(self):
        return {k: a for k, (kind, a) in self.files.items()
                if kind == "remove"}

    def summary(self) -> dict:
        """Comparable digest of the reconstructed state."""
        live = self.live
        return {
            "live_keys": sorted(f"{p}|{dv or ''}" for p, dv in live),
            "tombstone_keys": sorted(
                f"{p}|{dv or ''}" for p, dv in self.tombstones),
            "num_live": len(live),
            "live_bytes": sum(int(a.get("size") or 0) for a in live.values()),
            "protocol": self.protocol,
            "metadata_id": (self.metadata or {}).get("id"),
            "partition_columns": (self.metadata or {}).get(
                "partitionColumns"),
            "configuration": (self.metadata or {}).get("configuration"),
            "txns": dict(sorted(self.txns.items())),
            "domains": sorted(d for d, v in self.domains.items()
                              if not v.get("removed")),
            "latest_ict": self.latest_ict,
        }


def _row_to_action(name: str, row: dict) -> dict | None:
    """One non-null checkpoint struct column -> action dict (drop nulls
    so the shape matches commit JSON)."""

    def clean(v):
        if isinstance(v, dict):
            return {k: clean(x) for k, x in v.items() if x is not None}
        if isinstance(v, list):
            # Arrow map columns surface as [(k, v), ...] pair lists
            if v and all(isinstance(x, tuple) and len(x) == 2 for x in v):
                return {k: clean(x) for k, x in v}
            return [clean(x) for x in v]
        return v

    if row is None:
        return None
    return {name: clean(row)}


def _apply_checkpoint_file(state: OracleState, path: str,
                           log_dir: str) -> None:
    if path.endswith(".json"):
        with open(path) as f:
            rows = [json.loads(ln) for ln in f if ln.strip()]
    else:
        table = pq.read_table(path)
        rows = table.to_pylist()
    sidecars = []
    for row in rows:
        for name in ("txn", "domainMetadata", "metaData", "protocol",
                     "add", "remove"):
            if isinstance(row, dict) and row.get(name) is not None:
                act = _row_to_action(name, row[name])
                if act:
                    state.apply(act)
        if isinstance(row, dict) and row.get("sidecar") is not None:
            sidecars.append(row["sidecar"]["path"])
    for sc in sidecars:
        sc_path = sc if "/" in sc else os.path.join(log_dir, "_sidecars", sc)
        _apply_checkpoint_file(state, sc_path, log_dir)


def read_table_state(table_path: str, version: int | None = None) -> OracleState:
    """LIST the log, pick the newest usable checkpoint, replay ascending."""
    log_dir = os.path.join(table_path, "_delta_log")
    names = sorted(os.listdir(log_dir))

    commits = {}     # version -> filename
    compacted = []   # (lo, hi, filename)
    classic = {}     # version -> [filenames] (classic + multipart grouped)
    multi = {}       # (version, parts) -> {part: filename}
    v2 = {}          # version -> filename
    for name in names:
        m = _COMMIT_RE.match(name)
        if m:
            commits[int(m.group(1))] = name
            continue
        m = _COMPACT_RE.match(name)
        if m:
            compacted.append((int(m.group(1)), int(m.group(2)), name))
            continue
        m = _CLASSIC_CP_RE.match(name)
        if m:
            classic.setdefault(int(m.group(1)), []).append(name)
            continue
        m = _MULTI_CP_RE.match(name)
        if m:
            v, part, parts = int(m.group(1)), int(m.group(2)), int(m.group(3))
            multi.setdefault((v, parts), {})[part] = name
            continue
        m = _V2_CP_RE.match(name)
        if m:
            v = int(m.group(1))
            if version is None or v <= version:
                v2[v] = name

    # newest complete checkpoint at or below the target version
    candidates = []
    for v in classic:
        if version is None or v <= version:
            candidates.append((v, [classic[v][0]]))
    for (v, parts), got in multi.items():
        if (version is None or v <= version) and len(got) == parts:
            candidates.append((v, [got[p] for p in sorted(got)]))
    for v, name in v2.items():
        candidates.append((v, [name]))
    candidates.sort(key=lambda t: t[0])

    state = OracleState()
    cp_version = None
    if candidates:
        cp_version, cp_files = candidates[-1]
        for name in cp_files:
            _apply_checkpoint_file(state, os.path.join(log_dir, name),
                                   log_dir)

    start = 0 if cp_version is None else cp_version + 1
    target = version if version is not None else (
        max(commits) if commits else cp_version)
    v = start
    # compacted replacements: use a compacted file when it exactly covers
    # [v, hi] within range; else single commits
    comp_by_lo = {lo: (hi, name) for lo, hi, name in compacted}
    while target is not None and v <= target:
        if v in comp_by_lo and comp_by_lo[v][0] <= target:
            hi, name = comp_by_lo[v]
            path = os.path.join(log_dir, name)
            with open(path) as f:
                for ln in f:
                    if ln.strip():
                        state.apply(json.loads(ln))
            v = hi + 1
            continue
        if v not in commits:
            raise FileNotFoundError(f"missing commit {v}")
        with open(os.path.join(log_dir, commits[v])) as f:
            for ln in f:
                if ln.strip():
                    state.apply(json.loads(ln))
        v += 1
    return state
