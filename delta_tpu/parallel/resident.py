"""Device-resident sharded replay state across `Snapshot.update()`.

The sharded replay (`sharded_replay.py`) already rebuilds each shard's
key lane on device; the design assumes the expensive thing is the
host->device link, not the sort. So after a sharded full replay the
rebuilt per-shard key lane is simply KEPT on device (zero extra
transfer — `want_key` in the FA kernel), and every incremental
`Snapshot.update()` ships only its delta rows to their owning shards:
~8 bytes/delta row (slot index + key) instead of re-routing and
re-shipping the multi-million-row base state. The device then re-runs
the per-shard last-wins sort over base+delta and returns bit-packed
winner words (~1 bit/row D2H); the host — which keeps the add bits,
slot->row scatter, and path dictionary — rebuilds the full live and
tombstone masks without probing the base table at all.

Lifecycle: established by `compute_masks_device` (replay/state.py) when
the sharded route runs on DV-free input (in whatever row order the
parser left it: the payload maps slots to the caller's rows); ownership moves
`ColumnarActions` -> `SnapshotState` -> the advanced state (the append
kernel donates the key buffer, so exactly one state may own it);
released when a snapshot falls back to a full load (`table.py`), is
evicted from the serve cache (`serve/cache.py`), or is simply dropped:
residency ends with its owner, so a state nobody refers to any longer
gives its lanes back without a call (a finalizer of the resident state
releases the ledger entry; the buffers go with the last reference).
Any append the state
cannot express (DV rows, batches older than the resident tail, capacity
overflow) returns None and the caller falls back to the host delta
path, dropping residency; in-batch disorder is sorted away, not
rejected — real commits columnarize removes after adds. Disable with DELTA_TPU_RESIDENT=0.

The masks of an append are the last append's, patched: the state keeps
the winner words it read last and the two masks it returned, and an
append looks only at the words that differ (a delta of 100 rows can
change the winner of ~200 slots of millions), copies the two masks
once and writes the rows of those slots. With nothing to diff against
(the first append of a load: establishment unpacks nothing) both masks
are rebuilt over every slot, as they were at every append until PR 52.

What the chip found (PR 46, `ckpt-query-under-ingest-10m-v5e4`: 6.0M
rows on four v5e chips, the lanes really donated): every refresh took
route=resident and none fell back; an append is ~69 ms of a ~205 ms
refresh, ~56 of them on the host (`resident.masks` ~52: both masks
rebuilt over all `shards x m` slots) and ~13 waiting for the chips.
The host route beside it on one machine, and the verdict that pair
leaves open (ROADMAP C2): PERF.md §6. What the diff made of those 52 ms
(PR 52): PERF.md §6.
"""

from __future__ import annotations

import functools
import os
import threading
import weakref
from typing import Optional

import numpy as np

from delta_tpu import obs
from delta_tpu.obs import hbm

_H2D_BYTES = obs.counter("replay.h2d_bytes")
_APPENDS = obs.counter("replay.resident_appends")
_FALLBACKS = obs.counter("replay.resident_fallbacks")
_ESTABLISHED = obs.counter("replay.resident_established")
_RELEASED = obs.counter("replay.resident_released")
_MASK_DIFFS = obs.counter("replay.resident_mask_diffs")
_MASK_REBUILDS = obs.counter("replay.resident_mask_rebuilds")
# device bytes pinned by resident key lanes are accounted in the
# process-wide resident ledger (obs/hbm.py), which also derives the
# `replay.resident_hbm_bytes` gauge this module used to maintain


def enabled() -> bool:
    return os.environ.get("DELTA_TPU_RESIDENT") != "0"


@functools.lru_cache(maxsize=32)
def _append_fn_cached(mesh, d_pad: int):
    """jit'd per-mesh append+replay: scatter the delta keys into each
    shard's resident lane (slot indexes past the shard's capacity are
    the drop sentinel) and re-run the last-wins sort. The resident lane
    is donated — the update happens in place on device."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from delta_tpu.ops.replay import _sort_winner_pack
    from delta_tpu.parallel.mesh import REPLAY_AXIS
    from delta_tpu.parallel.sharded_replay import shard_map

    def kernel(key, idx, val, n_real):
        key, idx, val = key[0], idx[0], val[0]
        key = key.at[idx].set(val, mode="drop")
        winner = _sort_winner_pack((key,), n_real[0][0])
        return key[None], winner[None]

    spec = P(REPLAY_AXIS, None)
    fn = shard_map(kernel, mesh=mesh, in_specs=(spec,) * 4,
                   out_specs=(spec, spec))
    # donate the resident lane so the update is in place on device; CPU
    # backends don't implement donation and would warn on every call
    donate = (0,) if jax.default_backend() != "cpu" else ()
    return jax.jit(obs.program("replay.resident_append")(fn),
                   donate_argnums=donate)


def _dropped(handle) -> None:
    """Finalizer of a resident state that nobody released: its owner was
    dropped, which ends residency as `release()` does. The ledger entry
    goes as a release (an owner that ends is no leak); the buffers went
    with the last reference."""
    handle.release()
    _RELEASED.inc()


class ResidentShardState:
    """Host bookkeeping + device key lane for one resident snapshot."""

    def __init__(self, payload, paths, path_codes: np.ndarray):
        # payload: sharded_replay.ResidentPayload
        # Guards every post-publication mutation: append() rewrites the
        # slot bookkeeping and swaps the donated device lane, and
        # release() tears the lane down — the serve cache can evict (and
        # release) a snapshot while another thread's refresh is still
        # inside append(), so the two must serialize here, not rely on
        # callers holding the right entry lock.
        self._lock = threading.Lock()
        self.mesh = payload.mesh
        self.m = payload.m
        self.n_shards = int(payload.mesh.devices.size)
        self.key_sh = payload.key_sh
        self._hbm = hbm.register(
            self, kind=hbm.KIND_REPLAY_KEYS,
            arrays=(payload.key_sh,),
            rebuild_cost_class="expensive",  # full sharded replay
        )
        # registered after the ledger's own leak finalizer, so it runs
        # before it and leaves it nothing to report
        self._end = weakref.finalize(self, _dropped, self._hbm)
        self._end.atexit = False
        _ESTABLISHED.inc()
        self.n_real = np.asarray(payload.n_real, np.int64).copy()
        self.add = np.unpackbits(
            payload.add_words.view(np.uint8).reshape(self.n_shards, -1),
            axis=1, bitorder="little")[:, :self.m].astype(bool)
        self.scatter = payload.scatter.astype(np.int64)
        self.n = int(payload.n)
        self.n_uniq = int(payload.n_uniq)
        # path -> dense code, built lazily on first append (pd.Index
        # hashtable build is O(base), each append lookup O(delta))
        self._paths = paths            # arrow ChunkedArray, zero-copy ref
        self._base_codes = np.asarray(path_codes, np.uint32)
        self._index = None
        self._overlay: dict = {}       # paths first seen after establish
        self._max_version: Optional[int] = None  # newest appended version
        # (winner words, live, tomb) as the last append left them: what
        # the next one diffs against. None until an append has run, so a
        # load that is never refreshed unpacks nothing. The masks are the
        # owning snapshot's own arrays: references, never written to.
        self._last: Optional[tuple] = None

    # ------------------------------------------------------------ codes

    def _ensure_index(self) -> None:
        if self._index is not None:
            return
        import pandas as pd

        codes = self._base_codes
        n_base_uniq = int(codes.max()) + 1 if len(codes) else 0
        _, first_idx = np.unique(codes, return_index=True)
        paths_np = np.asarray(self._paths.to_pandas(), dtype=object)
        uniq_paths = paths_np[first_idx]
        assert len(uniq_paths) == n_base_uniq
        self._index = pd.Index(uniq_paths)
        self._paths = None             # dictionary built; drop the ref
        self._base_codes = None

    def _code_paths(self, delta_paths: list) -> np.ndarray:
        """Dense codes for the delta rows, extending the dictionary in
        first-appearance order (matching what a cold full replay's
        factorize would assign over concat(base, delta))."""
        self._ensure_index()
        codes = self._index.get_indexer(delta_paths)
        out = np.empty(len(delta_paths), np.uint32)
        for i, (p, c) in enumerate(zip(delta_paths, codes)):
            if c >= 0:
                out[i] = c
            else:
                c2 = self._overlay.get(p)
                if c2 is None:
                    c2 = self.n_uniq
                    self._overlay[p] = c2
                    self.n_uniq += 1
                out[i] = c2
        return out

    # ----------------------------------------------------------- append

    def append(self, delta_fa, n_prev: int):
        """Ship the delta rows to their shards, re-reconcile on device,
        and return (live_mask, tombstone_mask) over the concatenated
        n_prev + delta rows — or None when this state can't express the
        batch (caller falls back to the host delta path and drops
        residency)."""
        with self._lock:
            return self._append_locked(delta_fa, n_prev)

    def _append_locked(self, delta_fa, n_prev: int):
        from delta_tpu.ops.replay import chrono_ok

        d = delta_fa.num_rows
        if n_prev != self.n or self.key_sh is None:
            _FALLBACKS.inc()
            return None
        dv = delta_fa.column("dv_id")
        if dv.null_count != d:
            _FALLBACKS.inc()  # DV rows need the (path, dv) key: not resident
            return None
        version = np.asarray(delta_fa.column("version"), np.int64)
        order = np.asarray(delta_fa.column("order"), np.int32)
        # In-batch disorder is routine (a commit's removes serialize
        # before its adds but columnarize after), so sort here: the
        # device kernel breaks key ties by slot index, and slots are
        # assigned in processing order. Only a batch older than what's
        # already resident is inexpressible — appended slots always sort
        # after the base, so a stale version would win ties it lost.
        if chrono_ok(version, order):
            chrono = np.arange(d, dtype=np.int64)
        else:
            chrono = np.lexsort((order, version))
        if d:
            lo = int(version[chrono[0]])
            if self._max_version is not None and lo < self._max_version:
                _FALLBACKS.inc()
                return None

        # the phases below are spans under `on` from PHASE_SPAN_ROWS
        # rows held, as the host route's are (replay/state.py)
        small = self.n < obs.PHASE_SPAN_ROWS
        with obs.span("replay.resident_append", rows=d, base=self.n):
            if self._index is None:
                with obs.span("resident.index_build", _verbose=small,
                              rows=len(self._base_codes)):
                    self._ensure_index()
            with obs.span("resident.code_paths", _verbose=small,
                          rows=d) as ph:
                known = self.n_uniq
                codes = self._code_paths(
                    delta_fa.column("path").to_pylist())
                ph.set_attr("new_paths", self.n_uniq - known)
            with obs.span("resident.place", _verbose=small) as ph:
                is_add = np.asarray(delta_fa.column("is_add"), bool)
                codes_c = codes[chrono]
                is_add_c = is_add[chrono]
                s = self.n_shards
                shard_of = (codes_c % np.uint32(s)).astype(np.int64)
                counts = np.bincount(shard_of, minlength=s)
                new_n_real = self.n_real + counts
                if int(new_n_real.max(initial=0)) > self.m:
                    _FALLBACKS.inc()  # shard full: re-establish on next load
                    return None

                # slot of row i = shard fill level + rank among its
                # shard's delta rows (stable shard sort keeps
                # chronological order)
                sort_idx = np.argsort(shard_of, kind="stable")
                starts = np.zeros(s + 1, np.int64)
                np.cumsum(counts, out=starts[1:])
                rows = shard_of[sort_idx]
                slots = (np.arange(d) - starts[rows]) + self.n_real[rows]

                d_pad = max(128, 1 << int(d - 1).bit_length()) if d else 128
                idx2d = np.full((s, d_pad), self.m, np.int32)  # m = drop
                val2d = np.zeros((s, d_pad), np.uint32)
                cols = np.arange(d) - starts[rows]
                idx2d[rows, cols] = slots.astype(np.int32)
                val2d[rows, cols] = (codes_c[sort_idx] //
                                     np.uint32(s)).astype(np.uint32)
                ph.set_attr("d_pad", d_pad)

            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            from delta_tpu.parallel.mesh import REPLAY_AXIS

            spec = NamedSharding(self.mesh, P(REPLAY_AXIS, None))
            nbytes = idx2d.nbytes + val2d.nbytes
            _H2D_BYTES.inc(nbytes)
            obs.set_attrs(h2d_bytes=nbytes)
            n_real_op = new_n_real.astype(np.int32).reshape(s, 1)
            fn = _append_fn_cached(self.mesh, d_pad)
            with obs.device_dispatch("replay.resident_append",
                                     key=(s, d_pad),
                                     budget="resident-append",
                                     units=s * d_pad) as dd:
                dd.set(shards=s, m=self.m, d_pad=d_pad)
                dd.h2d("idx2d", idx2d)
                dd.h2d("val2d", val2d)
                dd.h2d("n_real_op", n_real_op)
                new_key, winner_sh = fn(
                    self.key_sh,
                    jax.device_put(idx2d, spec),
                    jax.device_put(val2d, spec),
                    jax.device_put(n_real_op, spec))
            self.key_sh = new_key
            # the donated append produced a NEW device array for the
            # same logical artifact: re-point the ledger's audit refs
            self._hbm.grow(arrays=(new_key,))

            # host bookkeeping for the appended slots (scatter maps each
            # slot back to its original arrow row, so the returned masks
            # stay in the caller's row order even for sorted batches)
            self.add[rows, slots] = is_add_c[sort_idx]
            self.scatter[rows, slots] = (n_prev +
                                         chrono[sort_idx].astype(np.int64))
            self.n_real = new_n_real
            self.n = n_prev + d
            if d:
                self._max_version = int(version[chrono[-1]])

            # the launch returned at once; this read is where the host
            # waits for the chips
            with obs.span("resident.wait", rows=self.n) as ph:
                winner_np = np.asarray(winner_sh)  # [S, M/32] packed D2H
                ph.set_attr("bytes", winner_np.nbytes)
            with obs.span("resident.masks", _verbose=small,
                          slots=s * self.m) as ph:
                live, tomb = self._masks(winner_np, n_prev, ph)
            self._last = (winner_np, live, tomb)
            _APPENDS.inc()
            return live, tomb

    def _masks(self, words: np.ndarray, n_prev: int, ph):
        """(live, tomb) over the `self.n` rows held, from the packed
        winner words `[shards, m / 32]` of the append just run. A slot's
        add bit and row never change once written, so its two mask bits
        change only where its winner bit does: the last append's masks,
        copied once, are patched at the slots under the words that
        differ from the last ones. A slot this delta filled was 0 in the
        last words (padding never wins), so it shows up there if it won
        and stays false if not. Both masks are rebuilt over every slot
        where the state holds nothing of this shape to diff against."""
        last_words, last_live, last_tomb = self._last or (None,) * 3
        if (last_words is None or last_words.shape != words.shape
                or len(last_live) != n_prev):
            winner = np.unpackbits(
                words.view(np.uint8).reshape(self.n_shards, -1),
                axis=1, bitorder="little")[:, :self.m].astype(bool)
            live_slots = winner & self.add
            tomb_slots = winner & ~self.add
            valid = self.scatter >= 0
            live = np.zeros(self.n, bool)
            tomb = np.zeros(self.n, bool)
            live[self.scatter[valid]] = live_slots[valid]
            tomb[self.scatter[valid]] = tomb_slots[valid]
            ph.set_attr("mode", "full")
            _MASK_REBUILDS.inc()
            return live, tomb

        # fresh arrays every time: a reader that holds the snapshot
        # before this one keeps the masks it was handed
        behind = np.zeros(self.n - n_prev, bool)
        live = np.concatenate([last_live, behind])
        tomb = np.concatenate([last_tomb, behind])
        changed = (words ^ last_words).ravel()
        at = np.flatnonzero(changed)
        bits = np.unpackbits(changed[at].view(np.uint8).reshape(-1, 4),
                             axis=1, bitorder="little")
        k, bit = np.nonzero(bits)       # the k-th changed word, and where
        won = (words.ravel()[at[k]] >> bit.astype(np.uint32)) & 1 != 0
        shard, word = np.divmod(at[k], words.shape[1])
        slot = word * 32 + bit
        row = self.scatter[shard, slot]
        is_add = self.add[shard, slot]
        live[row] = won & is_add
        tomb[row] = won & ~is_add
        ph.set_attrs(mode="diff", changed_words=len(at),
                     changed_slots=len(row))
        _MASK_DIFFS.inc()
        return live, tomb

    def device_hint(self):
        """First device of the owning mesh, or None once released — the
        checkpoint writer colocates its aggregation upload with the
        resident replay lanes so the stats dispatch lands on a device
        that already holds this snapshot's columnar state."""
        with self._lock:
            if self.key_sh is None or self.mesh is None:
                return None
            try:
                return self.mesh.devices.flat[0]
            # delta-lint: disable=except-swallow (audited: the hint is
            # a placement optimization — any mesh-shape drift must fall
            # back to default placement, never fail a checkpoint)
            except Exception:
                return None

    def release(self) -> None:
        """Drop the device buffer (the host bookkeeping is garbage with
        it, so the whole state is dead after this). Serializes against
        append(): an in-flight append finishes against the lane it
        started with before the release lands."""
        with self._lock:
            if self.key_sh is not None:
                self.key_sh = None
                self._hbm.release()
                self._end.detach()
                _RELEASED.inc()


def establish_resident(payload, file_actions,
                       path_codes: np.ndarray) -> Optional[ResidentShardState]:
    """Wrap a `ResidentPayload` from `sharded_replay_select` with the
    snapshot's path column so future appends can code new paths
    consistently. `file_actions` is the canonical arrow table the
    payload's rows came from (same row order)."""
    try:
        with obs.span("replay.resident_establish", rows=payload.n):
            # the chunks as they lie: a load that is never refreshed
            # (every cold load) pays for no copy of its paths
            return ResidentShardState(
                payload, file_actions.column("path"), path_codes)
    # delta-lint: disable=except-swallow (audited: residency is an
    # optimization; any establishment failure must degrade to the
    # non-resident path, never fail the load)
    except Exception:
        _FALLBACKS.inc()
        return None


def touch_snapshot_resident(snapshot) -> None:
    """Record access recency on a snapshot's resident artifacts (serve
    cache hits/refreshes route here). Duck-typed like
    `release_snapshot_resident`; missing pieces are no-ops."""
    state = getattr(snapshot, "_state", None) or snapshot
    resident = getattr(state, "resident", None)
    if resident is not None:
        resident._hbm.touch()
    stats_index = getattr(state, "stats_index", None)
    if stats_index is not None:
        stats_index._hbm.touch()
    operand_cache = getattr(state, "operand_cache", None)
    if operand_cache is not None:
        operand_cache._hbm.touch()


def release_snapshot_resident(snapshot) -> None:
    """Free a snapshot's resident device state, if any. Accepts
    `Snapshot`, `SnapshotState`, or anything in between (duck-typed so
    the serve cache and table fallback paths don't need type checks)."""
    state = getattr(snapshot, "_state", None) or snapshot
    resident = getattr(state, "resident", None)
    if resident is not None:
        resident.release()
        state.resident = None
    # the scan-planning stats index (stats/device_index.py) shares the
    # residency lifecycle: evicting the snapshot frees its lanes too,
    # and the seed of an index not built yet
    from delta_tpu.stats.device_index import release_state_stats_index

    release_state_stats_index(state)
    # the SQL operand cache (sqlengine/operands.py) shares the same
    # lifecycle: evicting the snapshot frees its column lanes too
    operand_cache = getattr(state, "operand_cache", None)
    if operand_cache is not None:
        operand_cache.release()
        state.operand_cache = None
