"""Pallas TPU kernels for the hottest per-row ops.

Two kernels with identical jnp fallbacks (used automatically off-TPU or
via `interpret=True` on CPU):

- `interleave_bits_tiled`: the OPTIMIZE ZORDER curve-key op. One VMEM
  pass per [8, 128] tile computes all output words — the 32·k-step bit
  loop stays in registers instead of materializing 32·k intermediate
  arrays for XLA to fuse.
- `segmented_minmax`: per-file min/max/count over a [files, rows] batch
  with a validity mask — the stats-collection reduction when many data
  files are written in one call (stats for the skipping index,
  `StatisticsCollection.scala:257` role).

Layout notes: rows are padded to 128 lanes; tiles are (8, 128) float32 /
int32 per the TPU tiling table.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl

_LANES = 128
_SUBLANES = 8
_TILE = _SUBLANES * _LANES


def _x32():
    """Scoped x32 context (`jax.enable_x64` takes the desired state)."""
    return jax.enable_x64(False)


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# interleave bits
# ---------------------------------------------------------------------------


def _interleave_kernel(n_cols: int, n_bits: int, n_words: int, in_ref, out_ref):
    """in_ref: [k, 8, 128] uint32; out_ref: [w, 8, 128] uint32."""
    cols = [in_ref[c] for c in range(n_cols)]
    words = [jnp.zeros((_SUBLANES, _LANES), jnp.uint32) for _ in range(n_words)]
    for g in range(n_cols * n_bits):
        c = g % n_cols
        s = n_bits - 1 - g // n_cols
        w, wb = divmod(g, 32)
        bit = (cols[c] >> jnp.uint32(s)) & jnp.uint32(1)
        words[w] = words[w] | (bit << jnp.uint32(31 - wb))
    for w in range(n_words):
        out_ref[w] = words[w]


@functools.partial(jax.jit, static_argnames=("n_bits",))
def interleave_bits_tiled(cols: jnp.ndarray, n_bits: int = 32) -> jnp.ndarray:
    """cols: [k, n] uint32 (n a multiple of 1024) -> [w, n] uint32."""
    k, n = cols.shape
    n_words = max(1, -(-(k * n_bits) // 32))
    assert n % _TILE == 0, n
    tiles = n // _TILE
    shaped = cols.reshape(k, tiles * _SUBLANES, _LANES)
    kernel = functools.partial(_interleave_kernel, k, n_bits, n_words)
    out = pl.pallas_call(
        kernel,
        grid=(tiles,),
        in_specs=[pl.BlockSpec((k, _SUBLANES, _LANES), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((n_words, _SUBLANES, _LANES), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_words, tiles * _SUBLANES, _LANES), jnp.uint32),
        interpret=_use_interpret(),
        name="interleave_bits_tiled",
    )(shaped)
    return out.reshape(n_words, n)


def interleave_bits_auto(cols, n_bits: int = 32):
    """Pallas when available/beneficial, jnp fallback otherwise.
    x32 pinned: Mosaic grid indexing is i32 and all dtypes here are
    explicit, so a global x64 flip (the SQL spine's) must not leak in."""
    from delta_tpu.ops.zorder import interleave_bits

    with _x32():
        stacked = jnp.stack(list(cols))
        k, n = stacked.shape
        if n % _TILE != 0:
            return interleave_bits(list(cols), n_bits=n_bits)
        return interleave_bits_tiled(stacked, n_bits=n_bits)


# ---------------------------------------------------------------------------
# segmented min/max/count (stats collection)
# ---------------------------------------------------------------------------


def _minmax_kernel(in_ref, mask_ref, min_ref, max_ref, cnt_ref):
    """in/mask: [8, R]; outputs: [8, 128] (stats broadcast into lane 0)."""
    x = in_ref[:]
    valid = mask_ref[:]
    big = jnp.float32(jnp.inf)
    mn = jnp.min(jnp.where(valid, x, big), axis=1, keepdims=True)
    mx = jnp.max(jnp.where(valid, x, -big), axis=1, keepdims=True)
    cnt = jnp.sum(valid.astype(jnp.float32), axis=1, keepdims=True)
    min_ref[:] = jnp.broadcast_to(mn, (_SUBLANES, _LANES))
    max_ref[:] = jnp.broadcast_to(mx, (_SUBLANES, _LANES))
    cnt_ref[:] = jnp.broadcast_to(cnt, (_SUBLANES, _LANES))


@jax.jit
def segmented_minmax(values: jnp.ndarray, valid: jnp.ndarray):
    """values/valid: [F, R] float32/bool, F a multiple of 8, R of 128.
    Returns (min[F], max[F], valid_count[F]) — min/max over valid entries
    (±inf when a file has no valid rows)."""
    f, r = values.shape
    assert f % _SUBLANES == 0 and r % _LANES == 0, (f, r)
    grid = (f // _SUBLANES,)
    spec_in = pl.BlockSpec((_SUBLANES, r), lambda i: (i, 0))
    spec_out = pl.BlockSpec((_SUBLANES, _LANES), lambda i: (i, 0))
    mn, mx, cnt = pl.pallas_call(
        _minmax_kernel,
        grid=grid,
        in_specs=[spec_in, spec_in],
        out_specs=(spec_out, spec_out, spec_out),
        out_shape=(
            jax.ShapeDtypeStruct((f, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((f, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((f, _LANES), jnp.float32),
        ),
        interpret=_use_interpret(),
        name="segmented_minmax",
    )(values.astype(jnp.float32), valid)
    return mn[:, 0], mx[:, 0], cnt[:, 0].astype(jnp.int32)


def batched_file_stats(values: np.ndarray, valid: np.ndarray):
    """Host wrapper: pad [F, R] to tile multiples, run the kernel, return
    numpy (min, max, null_count, num_records) per file. x32 pinned for
    the same Mosaic reason as interleave_bits_auto."""
    with _x32():
        return _batched_file_stats_impl(values, valid)


def _batched_file_stats_impl(values: np.ndarray, valid: np.ndarray):
    f, r = values.shape
    fpad = (-f) % _SUBLANES
    rpad = (-r) % _LANES
    v = np.pad(values.astype(np.float32), ((0, fpad), (0, rpad)))
    m = np.pad(valid.astype(bool), ((0, fpad), (0, rpad)))
    mn, mx, cnt = segmented_minmax(jnp.asarray(v), jnp.asarray(m))
    mn = np.asarray(mn)[:f]
    mx = np.asarray(mx)[:f]
    cnt = np.asarray(cnt)[:f]
    num_records = np.full(f, r, dtype=np.int64)
    null_count = num_records - cnt
    return mn, mx, null_count, num_records


# ---------------------------------------------------------------------------
# JSON structural byte classes (device action parse)
# ---------------------------------------------------------------------------

# uint8 min tile is (32, 128) per the TPU tiling table
_BYTE_SUBLANES = 32
_BYTE_TILE = _BYTE_SUBLANES * _LANES

# class bit per structural byte; ops/json_parse.py tests these bits
BYTE_CLASS_BITS = {
    "newline": 1, "quote": 2, "backslash": 4,
    "colon": 8, "lbrace": 16, "rbrace": 32,
}
_BYTE_CLASS_VALUES = ((10, 1), (34, 2), (92, 4), (58, 8), (123, 16),
                      (125, 32))


def _byte_class_kernel(in_ref, out_ref):
    """in/out: [32, 128] uint8. One VMEM pass ORs the six structural
    class bits per byte — the first stage of the device JSON parse
    (quote/escape/colon masks feed the parity scans in
    ops/json_parse.py)."""
    b = in_ref[:].astype(jnp.int32)
    cls = jnp.zeros_like(b)
    for byte, bit in _BYTE_CLASS_VALUES:
        cls = cls | jnp.where(b == jnp.int32(byte), jnp.int32(bit),
                              jnp.int32(0))
    out_ref[:] = cls.astype(jnp.uint8)


@jax.jit
def byte_class_tiled(b: jnp.ndarray) -> jnp.ndarray:
    """b: [n] uint8 (n a multiple of 4096) -> [n] uint8 class bitmask."""
    (n,) = b.shape
    assert n % _BYTE_TILE == 0, n
    tiles = n // _BYTE_TILE
    shaped = b.reshape(tiles * _BYTE_SUBLANES, _LANES)
    # the parse jit traces under x64; Mosaic index maps must stay i32
    with _x32():
        out = pl.pallas_call(
            _byte_class_kernel,
            grid=(tiles,),
            in_specs=[pl.BlockSpec((_BYTE_SUBLANES, _LANES),
                                   lambda i: (i, 0))],
            out_specs=pl.BlockSpec((_BYTE_SUBLANES, _LANES),
                                   lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct(
                (tiles * _BYTE_SUBLANES, _LANES), jnp.uint8),
            interpret=_use_interpret(),
            name="byte_class_tiled",
        )(shaped)
    return out.reshape(n)


# ---------------------------------------------------------------------------
# parquet bit-packed group decode (checkpoint page decoder)
# ---------------------------------------------------------------------------


def _check_unpack_width(w: int, allow_zero: bool = False) -> None:
    """Typed guard for the bit-unpack primitive. A corrupt page header
    can carry any width byte; before this guard a w>32 silently wrapped
    the value mask (`1 << w` mod 2^32) and decoded garbage."""
    lo = 0 if allow_zero else 1
    if not isinstance(w, (int, np.integer)) or not lo <= int(w) <= 32:
        from delta_tpu.errors import InvalidArgumentError

        raise InvalidArgumentError(
            f"bit-packed width must be in [{lo}, 32], got {w!r}")


def _unpack_kernel(w: int, in_ref, out_ref):
    """in_ref: [w, 8, 128] uint32 (word-index-major, like the
    interleave kernel's layout); out_ref: [32, 8, 128] uint32 values.

    One Parquet bit-packed GROUP is 32 values x w bits = w u32 words;
    value j of a group lives at bit j*w, so its word index j*w//32 and
    shift j*w%32 are STATIC per j — the 32-step loop unrolls into pure
    vector shifts/ors over the [8, 128] group tile (the exact inverse
    of `_interleave_kernel`)."""
    mask = jnp.uint32((1 << w) - 1) if w < 32 else jnp.uint32(0xFFFFFFFF)
    for j in range(32):
        bitpos = j * w
        lo, sh = divmod(bitpos, 32)
        v = in_ref[lo] >> jnp.uint32(sh)
        if sh + w > 32:
            v = v | (in_ref[lo + 1] << jnp.uint32(32 - sh))
        out_ref[j] = v & mask


@functools.partial(jax.jit, static_argnames=("w",))
def unpack_bitpacked_tiled(packed: jnp.ndarray, w: int) -> jnp.ndarray:
    """packed: [w, G] uint32 (word-major: packed[k, g] = word k of
    group g; G a multiple of 1024) -> [G * 32] uint32 values, group-
    major (value j of group g at g*32 + j)."""
    _check_unpack_width(w)
    g = packed.shape[1]
    assert g % _TILE == 0, g
    tiles = g // _TILE
    shaped = packed.reshape(w, tiles * _SUBLANES, _LANES)
    out = pl.pallas_call(
        functools.partial(_unpack_kernel, w),
        grid=(tiles,),
        in_specs=[pl.BlockSpec((w, _SUBLANES, _LANES), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((32, _SUBLANES, _LANES), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((32, tiles * _SUBLANES, _LANES),
                                       jnp.uint32),
        interpret=_use_interpret(),
        name="unpack_bitpacked_tiled",
    )(shaped)
    # [32, G] -> group-major [G, 32] -> flat
    return out.reshape(32, -1).T.reshape(-1)


def unpack_bitpacked(packed_words: np.ndarray, w: int,
                     n_groups: int, device=None) -> jnp.ndarray:
    """Decode `n_groups` Parquet bit-packed groups (32 values x w bits
    each) from a flat little-endian u32 word stream. Pallas when
    available, jnp fallback with identical semantics. Returns a device
    array of n_groups*32 uint32 values. w must be in [0, 32]; w == 0 is
    the valid all-zero run, anything outside raises
    InvalidArgumentError instead of wrapping the value mask."""
    _check_unpack_width(w, allow_zero=True)
    if w == 0:
        return jnp.zeros(n_groups * 32, jnp.uint32)
    need = n_groups * w
    padded_groups = -(-max(n_groups, 1) // _TILE) * _TILE
    buf = np.zeros(padded_groups * w, np.uint32)
    buf[:need] = packed_words[:need]
    # [G, w] group-major words -> [w, G] word-major for the kernel
    shaped = np.ascontiguousarray(buf.reshape(padded_groups, w).T)
    # Mosaic lowers this kernel with i32 grid indexing; a process that
    # enabled global x64 (the SQL spine does) would otherwise feed it
    # i64 index maps and fail to legalize — dtypes here are explicit,
    # so pin x32 semantics for the call
    with _x32():
        arr = jax.device_put(shaped, device)
        return unpack_bitpacked_tiled(arr, w)[:n_groups * 32]


# ---------------------------------------------------------------------------
# variable-shift bit-field extract (batched checkpoint page decode)
# ---------------------------------------------------------------------------
#
# The one-lane page decoder (ops/page_decode.py) turns every RLE/
# bit-packed hybrid position of a checkpoint part into four u32 lanes:
# the 32-bit little-endian window at the value's byte offset (`lo`),
# the spill byte above it (`hi`), the in-byte shift (`sh`, 0..7) and
# the run's bit width (`w`, 0..32). Unlike `unpack_bitpacked` the shift
# is DATA-dependent (each element belongs to a different run), so the
# extract is elementwise rather than a static unrolled group loop.


def _shift_extract_body(lo, hi, sh, w):
    """value = ((lo >> sh) | (hi << (32 - sh))) & mask(w), elementwise
    u32. `(32 - sh) & 31` + the sh>0 select keeps the sh==0 lane off
    the undefined 32-bit shift."""
    spill = jnp.where(sh > jnp.uint32(0),
                      hi << ((jnp.uint32(32) - sh) & jnp.uint32(31)),
                      jnp.uint32(0))
    mask = jnp.where(
        w >= jnp.uint32(32), jnp.uint32(0xFFFFFFFF),
        (jnp.uint32(1) << (w & jnp.uint32(31))) - jnp.uint32(1))
    return ((lo >> sh) | spill) & mask


def _shift_extract_kernel(lo_ref, hi_ref, sh_ref, w_ref, out_ref):
    """All refs: [8, 128] uint32 tiles; one VMEM pass per tile."""
    out_ref[:] = _shift_extract_body(lo_ref[:], hi_ref[:], sh_ref[:],
                                     w_ref[:])


@jax.jit
def shift_extract_tiled(lo: jnp.ndarray, hi: jnp.ndarray,
                        sh: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """lo/hi/sh/w: [n] uint32 (n a multiple of 1024) -> [n] uint32."""
    (n,) = lo.shape
    assert n % _TILE == 0, n
    tiles = n // _TILE
    spec = pl.BlockSpec((_SUBLANES, _LANES), lambda i: (i, 0))
    shaped = [a.reshape(tiles * _SUBLANES, _LANES)
              for a in (lo, hi, sh, w)]
    out = pl.pallas_call(
        _shift_extract_kernel,
        grid=(tiles,),
        in_specs=[spec, spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((tiles * _SUBLANES, _LANES),
                                       jnp.uint32),
        interpret=_use_interpret(),
        name="shift_extract_tiled",
    )(*shaped)
    return out.reshape(n)


def shift_extract(lo: jnp.ndarray, hi: jnp.ndarray, sh: jnp.ndarray,
                  w: jnp.ndarray, use_pallas: bool) -> jnp.ndarray:
    """Trace-time dispatcher used INSIDE the page-decode jit: the Pallas
    tile on TPU, the identical fused-jnp body elsewhere (interpret-mode
    Pallas inside a large jit would serialize the whole dispatch)."""
    if use_pallas and lo.shape[0] % _TILE == 0:
        return shift_extract_tiled(lo, hi, sh, w)
    return _shift_extract_body(lo, hi, sh, w)
