"""Pallas TPU kernels for the hottest per-row ops.

Three kernels, each with an identical jnp body (used off-TPU, or the
kernel itself runs under `interpret=True` on CPU):

- `interleave_bits_tiled`: the OPTIMIZE ZORDER curve-key op. One VMEM
  pass per [8, 128] tile computes all output words — the 32·k-step bit
  loop stays in registers instead of materializing 32·k intermediate
  arrays for XLA to fuse.
- `byte_class_tiled`: the six structural byte classes of the device
  JSON parse (`ops/json_parse.py`).
- `shift_extract_tiled`: the data-dependent bit-field extract of the
  batched checkpoint page decode (`ops/page_decode.py`).

Layout notes: rows are padded to 128 lanes; tiles are (8, 128) uint32
and (32, 128) uint8 per the TPU tiling table.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

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
# variable-shift bit-field extract (batched checkpoint page decode)
# ---------------------------------------------------------------------------
#
# The one-lane page decoder (ops/page_decode.py) turns every RLE/
# bit-packed hybrid position of a checkpoint part into four u32 lanes:
# the 32-bit little-endian window at the value's byte offset (`lo`),
# the spill byte above it (`hi`), the in-byte shift (`sh`, 0..7) and
# the run's bit width (`w`, 0..32). The shift is DATA-dependent (each
# element belongs to a different run), so the extract is elementwise
# rather than a static unrolled group loop.


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
