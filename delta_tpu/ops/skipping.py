"""Batched data-skipping kernel: one dispatch over the whole conjunct
list x file-stats table (reference `stats/DataSkippingReader.scala`
constructDataFilters, here compiled instead of interpreted).

`stats/device_index.py` columnarizes the snapshot's parsed file stats
into an int64 lane matrix (3 rows per skipping-eligible column: min /
max / nullCount, plus one trailing numRecords row) with a validity
bitplane, resident on device across scans of one snapshot version. The
chip has no 64-bit integers, so the resident form is the one it
computes on: the high halves `int32`, the low halves `uint32` and the
validity `bool`, each `[R, n_pad / 128, 128]`, split once an upload (an
int64 operand is split by the compiler at every launch, every lane of
it), and laid out so that a lane row is a slice on the major axis:
contiguous bytes and whole `(8, 128)` tiles (a row of a `[R, n_pad]`
matrix is one sublane of every tile). A scan's conjunct list is
compiled into flat *atom* arrays — one atom per `col op lit`
comparison, grouped so that OR-alternatives share a group id — and
this module evaluates every atom against every file in ONE jitted
call, in place: for each atom slot it reads that atom's three stat rows
where they lie in the resident arrays (a one-row `dynamic_slice`, which
XLA fuses into the comparison), applies the per-op "known false"
predicate to one row of files, and folds it into two carried flags a
file — "every atom of the open group so far is known false" and "some
closed group skips" — so temporaries are O(n_pad) whatever the number
of atoms, and no `[atoms, n_pad]` copy of the lanes is ever made. Slots
come in buckets from 2 (2, 4, 8, ...): a two-atom range plan runs a
two-slot program, and rows, op codes and literals are run-time
arguments, never compile keys (one bool D2H).

Kleene semantics match the host Arrow path by construction: an atom is
*known false* for a file only when the deciding stat is present and
proves no row can match; anything unknown keeps the file. A group
(OR of atoms) skips only when every atom is known false; the final
mask is the AND over groups. All lane math is int64's order (floats are
pre-encoded into order-preserving int64 by the index builder): the
numpy twin below compares the host's int64 lanes, the kernel their
halves as pairs (`a > b` is `(ah > bh) | ((ah == bh) & (al > bl))`,
signed on the high half, unsigned on the low), and both read the ops
off one table (`_known_false`), so the masks are bit-identical and
routing is a pure performance decision (`parallel/gate.py::skip_route`).

Atom op codes:
  0 '<'   1 '<='   2 '>'   3 '>='   4 '='   5 '!='
  6 IS NULL        7 IS NOT NULL
Ops 0-5 additionally treat an all-null column (nullCount == numRecords)
as known false, mirroring the host path's not-all-null augmentation.

This module performs no `jax.device_put`: the resident lanes are
uploaded by the budgeted site in `stats/device_index.py`, and the
per-scan atom arrays (~28 B per atom) ride along as jit arguments.
"""

from __future__ import annotations

import functools
import math
import operator
from typing import NamedTuple

import numpy as np

from delta_tpu import obs


class AtomBlock(NamedTuple):
    """Compiled conjunct list: flat atom arrays over the lane matrix.

    `rows_mn/rows_mx/rows_nc` index lane-matrix rows (the index builder
    lays column c out as rows 3c/3c+1/3c+2, numRecords last); `grp`
    assigns each atom to an OR-group; groups are ANDed into the mask.
    A group's atoms are adjacent (`compile_conjuncts` emits them group
    by group, ids dense and ascending): the device kernel closes a
    group where `grp` changes.
    """

    rows_mn: np.ndarray  # int32 [A] min-lane row per atom
    rows_mx: np.ndarray  # int32 [A] max-lane row per atom
    rows_nc: np.ndarray  # int32 [A] nullCount-lane row per atom
    ops: np.ndarray      # int32 [A] op code (see module docstring)
    lits: np.ndarray     # int64 [A] encoded literal (0 for ops 6/7)
    grp: np.ndarray      # int32 [A] OR-group id, dense in [0, n_groups)
    n_atoms: int
    n_groups: int
    # for the plan's span, read by no kernel: the atoms on `decimal`
    # lanes, and the conjuncts that were an OR over ANDs, distributed
    decimal_atoms: int = 0
    distributed: int = 0


def _known_false(xp, ge, gt, eq, zero, mn, mx, nc, nr, vmn, vmx, vnc, vnr,
                 op, lit):
    """Per-atom x per-file "stats prove no row matches" matrix.

    The one table of the eight ops, shared by the jit kernel and the
    numpy twin. `xp` is jax.numpy or numpy; `ge`, `gt`, `eq` and `zero`
    are the caller's order over whatever form its values have (int64's
    own for the twin, `_PAIR_ORDER` over 32-bit halves for the kernel),
    so both backends decide every comparison by the same int64 order and
    produce bit-identical results. Operands come already broadcastable.
    """
    all_null = vnc & vnr & eq(nc, nr)
    kf = xp.where(op == 0, vmn & ge(mn, lit),
         xp.where(op == 1, vmn & gt(mn, lit),
         xp.where(op == 2, vmx & ge(lit, mx),
         xp.where(op == 3, vmx & gt(lit, mx),
         xp.where(op == 4, (vmn & gt(mn, lit)) | (vmx & gt(lit, mx)),
         xp.where(op == 5, vmn & vmx & eq(mn, lit) & eq(mx, lit),
         xp.where(op == 6, vnc & eq(nc, zero),
                  all_null)))))))
    return kf | ((op <= 5) & all_null)


# int64's own order, for the numpy twin
_INT64_ORDER = (operator.ge, operator.gt, operator.eq, 0)


def _pair_gt(a, b):
    return (a[0] > b[0]) | ((a[0] == b[0]) & (a[1] > b[1]))


def _pair_ge(a, b):
    return (a[0] > b[0]) | ((a[0] == b[0]) & (a[1] >= b[1]))


def _pair_eq(a, b):
    return (a[0] == b[0]) & (a[1] == b[1])


# the same order over (int32 high half, uint32 low half) pairs: signed on
# the high half, unsigned on the low
_PAIR_ORDER = (_pair_ge, _pair_gt, _pair_eq, (0, 0))


@functools.lru_cache(maxsize=32)
def _skip_fn_cached(a_pad: int):
    """jit'd keep-mask kernel for `a_pad` atom slots, unrolled: each
    slot is a handful of fused elementwise passes over its own rows."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def row(lanes, r):
        return lax.dynamic_slice_in_dim(lanes, r, 1, axis=0)

    @obs.program("skipping.mask_block")
    def kernel(high, low, valid, rows_mn, rows_mx, rows_nc, ops, lits_high,
               lits_low, grp, n_atoms):
        def pair(r):
            return row(high, r), row(low, r)

        nr, vnr = (high[-1:], low[-1:]), valid[-1:]
        # the open group: every atom so far known false; and whether a
        # group closed before it skips the file
        open_kf = jnp.ones(vnr.shape, dtype=bool)
        skip = jnp.zeros(vnr.shape, dtype=bool)
        for i in range(a_pad):
            kf = _known_false(
                jnp, *_PAIR_ORDER, pair(rows_mn[i]), pair(rows_mx[i]),
                pair(rows_nc[i]), nr, row(valid, rows_mn[i]),
                row(valid, rows_mx[i]), row(valid, rows_nc[i]), vnr,
                ops[i], (lits_high[i], lits_low[i]))
            # pad slots are one trailing group whose atoms are never
            # known false: it closes the last real group, skips nothing
            kf = kf & (i < n_atoms)
            if i:
                closes = grp[i] != grp[i - 1]
                skip = skip | (closes & open_kf)
                open_kf = open_kf | closes
            open_kf = open_kf & kf
        return ~(skip | open_kf).reshape(-1)

    return jax.jit(kernel)


def skip_mask_block(dev_high, dev_low, dev_valid, block: AtomBlock,
                    n_files: int) -> np.ndarray:
    """Evaluate a compiled conjunct list against resident device lanes;
    one dispatch, one bool-mask D2H. `dev_high` / `dev_low` /
    `dev_valid` are the index's device arrays `[R, F_pad / 128, 128]`
    (`ResidentStatsIndex.device_lanes`)."""
    from delta_tpu.ops.replay import pad_bucket

    a_pad = pad_bucket(block.n_atoms, min_bucket=2)

    def _pad(a, fill, dtype):
        out = np.full(a_pad, fill, dtype=dtype)
        out[: block.n_atoms] = a
        return out

    rows_mn = _pad(block.rows_mn, 0, np.int32)
    rows_mx = _pad(block.rows_mx, 0, np.int32)
    rows_nc = _pad(block.rows_nc, 0, np.int32)
    ops = _pad(block.ops, 0, np.int32)
    lits = _pad(block.lits, 0, np.int64)
    lits_high, lits_low = (lits >> 32).astype(np.int32), lits.astype(np.uint32)
    grp = _pad(block.grp, block.n_groups, np.int32)
    # the index lanes are HBM-resident (budgeted at upload in
    # stats/device_index.py); the per-scan atom arrays ride as jit
    # arguments, so this dispatch carries no budgeted device_put lane
    with obs.device_dispatch("skipping.mask_block", key=a_pad,
                             gate="skip") as dd:
        # the resident lanes' shape: what a reader needs to count the
        # bytes this launch has to move
        dd.set(lanes=dev_high.shape[0], n_pad=math.prod(dev_high.shape[1:]))
        keep = _skip_fn_cached(a_pad)(
            dev_high, dev_low, dev_valid, rows_mn, rows_mx, rows_nc, ops,
            lits_high, lits_low, grp, np.int32(block.n_atoms))
        dd.d2h("keep", keep.nbytes)
    # the launch returns at once; the kernel's time is this read's
    # (`a_pad` - `atoms` of its slots were padding). `rows_read`: the
    # distinct lane rows the atoms name, and numRecords: what of the
    # index this launch has to read, however wide the index is
    rows_read = 1 + len(np.unique(np.concatenate(
        [block.rows_mn, block.rows_mx, block.rows_nc])))
    with obs.span("skip.wait", rows=n_files, bytes=keep.nbytes,
                  atoms=block.n_atoms, a_pad=a_pad,
                  rows_read=rows_read), dd.wait():
        return np.asarray(keep)[:n_files]


def host_skip_mask(vals: np.ndarray, valid: np.ndarray, block: AtomBlock,
                   n_files: int) -> np.ndarray:
    """numpy twin of the device kernel: identical formulas over the
    identical int64 lanes, so masks are bit-identical across routes."""
    vals = vals[:, :n_files]
    valid = valid[:, :n_files]
    mn, mx, nc = vals[block.rows_mn], vals[block.rows_mx], vals[block.rows_nc]
    vmn, vmx, vnc = (valid[block.rows_mn], valid[block.rows_mx],
                     valid[block.rows_nc])
    nr, vnr = vals[-1][None, :], valid[-1][None, :]
    kf = _known_false(np, *_INT64_ORDER, mn, mx, nc, nr, vmn, vmx, vnc, vnr,
                      block.ops[:, None], block.lits[:, None])
    keep = np.ones(n_files, dtype=bool)
    for g in range(block.n_groups):
        members = block.grp == g
        if members.any():
            keep &= ~kf[members].all(axis=0)
    return keep
