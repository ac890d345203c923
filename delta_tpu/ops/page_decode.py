"""Batched one-lane checkpoint page decode (the paper's 4th kernel).

`log/page_decode.py` owns the host side: it walks a checkpoint part's
projected column chunks, decompresses pages, parses the tiny varint run
headers of every RLE/bit-packed hybrid stream, and packs ALL page
payloads (data pages + dictionary pages + def-level streams + synthetic
path-dictionary remap tables) into ONE padded uint8 byte lane plus two
int32 plan lanes. This module owns the device side: a single cached-jit
dispatch per part decodes every hybrid position, expands def-levels to
a validity mask, gathers dictionary/PLAIN values, and — when the part's
path columns are cleanly dictionary-coded — compacts the replay-key
code lanes device-side so they NEVER round-trip through the host.

Plan layout (all int32):

run_plan[R, 6]   per hybrid run: global hybrid start, value count,
                 absolute lane bit offset, bit width, is_rle, rle value
                 (u32 bit pattern).
page_plan[P, 11] per data page: global output row start, row count,
                 max def level, def-stream hybrid start, kind
                 (PLAIN/BOOL/DICT), value byte offset, item size,
                 aux hybrid start (dict-index or bool bit stream),
                 dictionary byte offset, dictionary size, key column
                 flag (0 none / 1 add.path / 2 remove.path).

Everything is host-precomputed and static-shaped (pad_bucket), so the
whole decode is ONE dispatch per part: hybrid extract (Pallas tile on
TPU via `shift_extract`, fused jnp elsewhere) -> per-row def-level
lookup + present-rank cumsum -> byte gathers. int64/double values leave
as two u32 lanes combined host-side, which keeps the kernel x32-clean
for Mosaic.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np

from delta_tpu import obs
from delta_tpu.obs import hbm

# run_plan columns
R_H, R_N, R_BIT, R_W, R_RLE, R_VAL = range(6)
RUN_F = 6
# page_plan columns
(PG_OUT, PG_N, PG_MAXDEF, PG_DEFH, PG_KIND, PG_VALB, PG_ITEM, PG_AUXH,
 PG_DICTB, PG_DICTN, PG_KEY) = range(11)
PAGE_F = 11

KIND_PLAIN = 0
KIND_BOOL = 1
KIND_DICT = 2

KEY_NONE = 0
KEY_ADD = 1
KEY_REMOVE = 2

# searchsorted sentinel for plan padding rows: larger than any real
# hybrid/row index, far below int32 overflow
_FAR = 0x3FFFFFFF

# byte-lane cap so every absolute bit offset fits int32 (8*B < 2^31);
# a part beyond this falls back to Arrow whole-part
MAX_LANE_BYTES = 192 << 20

_OBS_HANDOFFS = obs.counter("decode.handoff_launches")


@dataclass
class PartPlan:
    """Host-built decode plan for one checkpoint part (see module doc
    for the lane layouts). Array shapes are already bucket-padded."""

    lane: np.ndarray       # uint8[B_pad]
    runs: np.ndarray       # int32[R_pad, RUN_F]
    pages: np.ndarray      # int32[P_pad, PAGE_F]
    h_total: int           # real hybrid positions (pre-pad)
    n_rows: int            # real output rows across all planned columns
    has_keys: bool         # any KEY_ADD/KEY_REMOVE pages present


@dataclass
class PartKeys:
    """Device-resident replay-key handoff for one part: part-local path
    codes compacted into (add rows, remove rows, pad) order. `codes`
    stays a device array — the handoff launcher remaps and consumes it
    without a host round trip."""

    codes: object          # jax u32[K_pad] device array (None if empty)
    n_add: int
    n_rem: int
    n_bad: int             # struct-present rows with a null path
    uniq: List[bytes]      # part-local dictionary, code order, raw bytes
    n_rows: int
    # resident-ledger handle for the device code lane; released by
    # `release_part_keys` when the handoff consumes or abandons it
    hbm: object = None


def _decode_stage_hybrid(lane, runs, h_pad: int, use_pallas: bool):
    import jax.numpy as jnp

    from delta_tpu.ops.pallas_kernels import shift_extract

    h = jnp.arange(h_pad, dtype=jnp.int32)
    run_h = runs[:, R_H]
    rid = jnp.clip(jnp.searchsorted(run_h, h, side="right") - 1,
                   0, runs.shape[0] - 1).astype(jnp.int32)
    row = runs[rid]
    j = jnp.clip(h - row[:, R_H], 0, row[:, R_N])
    w = row[:, R_W]
    bit = row[:, R_BIT] + j * w
    byte0 = bit >> 3
    b_max = lane.shape[0] - 1
    gb = [lane[jnp.clip(byte0 + k, 0, b_max)].astype(jnp.uint32)
          for k in range(5)]
    lo = gb[0] | (gb[1] << 8) | (gb[2] << 16) | (gb[3] << 24)
    val = shift_extract(lo, gb[4], (bit & 7).astype(jnp.uint32),
                        w.astype(jnp.uint32), use_pallas)
    return jnp.where(row[:, R_RLE] == 1, row[:, R_VAL].astype(jnp.uint32),
                     val)


@functools.lru_cache(maxsize=32)
def _decode_fn(b_pad: int, r_pad: int, p_pad: int, h_pad: int,
               n_pad: int, k_pad: int, has_keys: bool, use_pallas: bool):
    import jax
    import jax.numpy as jnp

    @obs.program("page_decode.part")
    def fn(lane, runs, pages):
        hyb = _decode_stage_hybrid(lane, runs, h_pad, use_pallas)

        i = jnp.arange(n_pad, dtype=jnp.int32)
        pid = jnp.clip(jnp.searchsorted(pages[:, PG_OUT], i, side="right")
                       - 1, 0, p_pad - 1).astype(jnp.int32)
        pg = pages[pid]
        j = i - pg[:, PG_OUT]
        in_page = (j >= 0) & (j < pg[:, PG_N])
        maxdef = pg[:, PG_MAXDEF]
        h_max = h_pad - 1
        jc = jnp.clip(j, 0, _FAR)
        lvl = jnp.where(
            maxdef > 0,
            hyb[jnp.clip(pg[:, PG_DEFH] + jc, 0, h_max)].astype(jnp.int32),
            maxdef)
        defined = in_page & (lvl == maxdef)

        cdef = jnp.cumsum(defined.astype(jnp.int32))
        out0 = pg[:, PG_OUT]
        base = jnp.where(out0 > 0, cdef[jnp.clip(out0 - 1, 0, n_pad - 1)],
                         0)
        p = jnp.clip(cdef - 1 - base, 0, _FAR)

        kind = pg[:, PG_KIND]
        aux = hyb[jnp.clip(pg[:, PG_AUXH] + p, 0, h_max)]
        item = pg[:, PG_ITEM]
        idx = jnp.clip(aux.astype(jnp.int32), 0,
                       jnp.maximum(pg[:, PG_DICTN] - 1, 0))
        src = jnp.where(kind == KIND_DICT,
                        pg[:, PG_DICTB] + idx * item,
                        pg[:, PG_VALB] + p * item)
        b_max = b_pad - 1
        vb = [lane[jnp.clip(src + k, 0, b_max)].astype(jnp.uint32)
              for k in range(8)]
        lo = vb[0] | (vb[1] << 8) | (vb[2] << 16) | (vb[3] << 24)
        hi = vb[4] | (vb[5] << 8) | (vb[6] << 16) | (vb[7] << 24)
        lo = jnp.where(kind == KIND_BOOL, aux, lo)
        hi = jnp.where((kind != KIND_BOOL) & (item == 8), hi,
                       jnp.uint32(0))
        zero = jnp.uint32(0)
        out_lo = jnp.where(defined, lo, zero)
        out_hi = jnp.where(defined, hi, zero)
        if not has_keys:
            return out_lo, out_hi, defined

        key_col = pg[:, PG_KEY]
        struct_ok = lvl >= maxdef - 1
        pres_a = in_page & (key_col == KEY_ADD) & struct_ok
        pres_r = in_page & (key_col == KEY_REMOVE) & struct_ok
        bad = (pres_a | pres_r) & (lvl < maxdef)
        n_add = jnp.sum(pres_a.astype(jnp.int32))
        n_rem = jnp.sum(pres_r.astype(jnp.int32))
        n_bad = jnp.sum(bad.astype(jnp.int32))
        rank_a = jnp.cumsum(pres_a.astype(jnp.int32)) - 1
        rank_r = jnp.cumsum(pres_r.astype(jnp.int32)) - 1
        pos = jnp.where(pres_a, rank_a,
                        jnp.where(pres_r, n_add + rank_r, k_pad))
        codes = jnp.full((k_pad,), 0xFFFFFFFF,
                         jnp.uint32).at[pos].set(lo, mode="drop")
        return (out_lo, out_hi, defined, codes,
                jnp.stack([n_add, n_rem, n_bad]))

    return jax.jit(fn)


def decode_part(plan: PartPlan, device=None
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                           Optional[PartKeys]]:
    """Run the one-dispatch decode for `plan`. Returns (lo, hi, defined)
    numpy lanes over the plan's global row space plus the device-
    resident PartKeys (None when the plan carries no key pages).

    One H2D per lane of the `ckpt-page-decode` budget entry; values and
    validity return as dense D2H blocks, key codes STAY on device (only
    the three count scalars come back)."""
    import jax

    from delta_tpu.ops.pallas_kernels import _TILE, _use_interpret, _x32

    lane_bytes = np.asarray(plan.lane, np.uint8)
    run_plan = np.asarray(plan.runs, np.int32)
    page_plan = np.asarray(plan.pages, np.int32)
    b_pad = lane_bytes.shape[0]
    r_pad, p_pad = run_plan.shape[0], page_plan.shape[0]
    from delta_tpu.ops.replay import pad_bucket

    h_pad = pad_bucket(plan.h_total)
    n_pad = pad_bucket(plan.n_rows)
    k_pad = pad_bucket(plan.n_rows)
    use_pallas = not _use_interpret() and h_pad % _TILE == 0
    fn = _decode_fn(b_pad, r_pad, p_pad, h_pad, n_pad, k_pad,
                    plan.has_keys, use_pallas)
    with obs.device_dispatch(
            "page_decode.part",
            key=(b_pad, r_pad, p_pad, h_pad, n_pad, plan.has_keys),
            budget="ckpt-page-decode", units=b_pad,
            gate="decode") as dd, _x32():
        dd.h2d("lane_bytes", lane_bytes)
        dd.h2d("run_plan", run_plan, units=run_plan.size)
        dd.h2d("page_plan", page_plan, units=page_plan.size)
        outs = fn(jax.device_put(lane_bytes, device),
                  jax.device_put(run_plan, device),
                  jax.device_put(page_plan, device))
        lo = np.asarray(dd.d2h("out_lo", outs[0]))
        hi = np.asarray(dd.d2h("out_hi", outs[1]))
        defined = np.asarray(dd.d2h("defined", outs[2]))
        keys = None
        if plan.has_keys:
            counts = np.asarray(dd.d2h("key_counts", outs[4]))
            keys = PartKeys(codes=outs[3], n_add=int(counts[0]),
                            n_rem=int(counts[1]), n_bad=int(counts[2]),
                            uniq=[], n_rows=plan.n_rows)
            keys.hbm = hbm.register(
                keys, kind=hbm.KIND_CKPT_HANDOFF, arrays=(outs[3],),
                rebuild_cost_class="cheap",  # re-decode of one part
            )
    return lo, hi, defined, keys


# ---------------------------------------------------------------- handoff --


def release_part_keys(parts: Sequence[PartKeys]) -> None:
    """Deregister the device code lanes of `parts` — they were either
    consumed by a launched handoff or abandoned (handoff disqualified,
    route not chosen); either way the artifact's residency ends here."""
    for p in parts:
        if p.hbm is not None:
            p.hbm.release()
            p.hbm = None


def _decoded_paths(raw: Sequence[bytes]) -> Optional[List[str]]:
    """Decode raw path bytes with the same RFC 2396 percent-decoding the
    columnarizer applies (`replay/columnar.py::_decode_paths`); None on
    non-utf8 bytes (caller disqualifies the handoff)."""
    try:
        out = [b.decode("utf-8") for b in raw]
    except UnicodeDecodeError:
        return None
    if any("%" in s for s in out):
        from urllib.parse import unquote

        out = [unquote(s) if "%" in s else s for s in out]
    return out


@functools.lru_cache(maxsize=16)
def _handoff_fn(m: int, k_pads: tuple):
    import jax
    import jax.numpy as jnp

    from delta_tpu.ops.replay import _sort_winner_pack

    @obs.program("page_decode.handoff")
    def fn(remap, meta, n_real, *code_lanes):
        out = jnp.full((m,), 0xFFFFFFFF, jnp.uint32)
        for i, codes in enumerate(code_lanes):
            local = jnp.clip(codes.astype(jnp.int32), 0,
                             jnp.maximum(meta[i, 1] - 1, 0))
            g = remap[jnp.clip(meta[i, 0] + local, 0,
                               remap.shape[0] - 1)]
            kidx = jnp.arange(codes.shape[0], dtype=jnp.int32)
            pos = jnp.where(kidx < meta[i, 3], meta[i, 2] + kidx, m)
            out = out.at[pos].set(g, mode="drop")
        return _sort_winner_pack((out,), n_real)

    return jax.jit(fn)


def launch_checkpoint_handoff(parts: Sequence[PartKeys], engine=None,
                              device=None):
    """Launch the checkpoint-only replay straight from device-resident
    part key lanes. Returns an `ops.replay.ReplayPending` (the device
    sorts while the host assembles the Arrow table) or None when the
    plain single-chip kernel isn't what the gate picks for `engine` /
    the parts disqualify.

    Host work is O(unique paths): per-part dictionaries unify into one
    global code space and only the tiny uint32 remap tables cross the
    link — the O(rows) key lanes never leave the device. Row order is
    (part order) x (add block, remove block), exactly how the
    columnarizer concatenates checkpoint blocks, and a checkpoint holds
    at most one action per (path, dvId), so the synthetic chronological
    rank can never change a winner."""
    import jax

    from delta_tpu.ops.pallas_kernels import _x32
    from delta_tpu.ops.replay import ReplayPending, _pack_bits, pad_bucket
    from delta_tpu.parallel import gate

    # the launch consumes (or abandons) every part's code lane
    # on every return path below — residency ends with this call
    try:
        live = [p for p in parts if p.n_add + p.n_rem > 0]
        n = sum(p.n_add + p.n_rem for p in live)
        if not live or n == 0:
            return None
        if any(p.n_bad > 0 or p.codes is None for p in live):
            return None
        if gate.replay_kernel(n, engine) != "single":
            return None

        # global path-code unification over RAW dictionary bytes, with the
        # percent-decoded collision check (two raw spellings of one decoded
        # path must share a replay code — rare, so just disqualify)
        global_codes: dict = {}
        remaps: List[np.ndarray] = []
        offs: List[int] = []
        off = 0
        for p in live:
            decoded = _decoded_paths(p.uniq)
            if decoded is None:
                return None
            remap = np.empty(max(len(decoded), 1), np.uint32)
            for j, s in enumerate(decoded):
                remap[j] = global_codes.setdefault(s, len(global_codes))
            offs.append(off)
            remaps.append(remap)
            off += remap.shape[0]
        if len(global_codes) >= 0xFFFFFFFF:
            return None

        m = pad_bucket(n)
        r_pad = pad_bucket(off, min_bucket=128)
        remap_lane = np.zeros(r_pad, np.uint32)
        remap_lane[:off] = np.concatenate(remaps)
        part_meta = np.zeros((len(live), 4), np.int32)
        is_add = np.zeros(m, np.bool_)
        row = 0
        for i, p in enumerate(live):
            part_meta[i] = (offs[i], remaps[i].shape[0], row,
                            p.n_add + p.n_rem)
            is_add[row:row + p.n_add] = True
            row += p.n_add + p.n_rem
        add_words = _pack_bits(is_add)

        k_pads = tuple(int(p.codes.shape[0]) for p in live)
        fn = _handoff_fn(m, k_pads)
        with obs.device_dispatch("page_decode.handoff", key=(m, k_pads),
                                 budget="ckpt-decode-handoff", units=r_pad,
                                 gate="replay", route="single") as dd, _x32():
            dd.h2d("remap_lane", remap_lane)
            dd.h2d("part_meta", part_meta, units=part_meta.size)
            winner = fn(jax.device_put(remap_lane, device),
                        jax.device_put(part_meta, device),
                        np.int32(n), *[p.codes for p in live])
        _OBS_HANDOFFS.inc()
        return ReplayPending(winner, add_words, n, None, dispatch=dd)
    finally:
        release_part_keys(parts)
