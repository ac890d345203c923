"""Device JSON field extraction for the commit-replay hot path.

PAPER.md names JSON action parsing as one of the components that "must
become XLA/Pallas device kernels — not Python loops"; the host's
alternative is the per-byte C++ field-extraction scan, which the gate
prices at 270 MB/s (a placeholder, not measured on this device:
`parallel/gate.py`). This module is the device half of that lever:
one contiguous newline-terminated commit-window byte buffer ships to
device as a single uint8 lane (the `json-parse-window` plane in
`resources/transfer_budget.json`), and a batched data-parallel pass
extracts the replay-critical fields of every *simple* add/remove line
at once:

- structural scan: quote/escape/colon/brace masks, backslash-run
  parity for escape initiators, in-string parity from unescaped
  quotes, brace depth (the byte-class stage runs as a Pallas kernel on
  TPU — `ops/pallas_kernels.py::byte_class_tiled` — with an identical
  jnp fallback);
- key-fingerprint match: shifted byte compares locate the known
  depth-2 keys (`"path"`, `"size"`, `"modificationTime"`,
  `"dataChange"`, `"deletionTimestamp"`, `"extendedFileMetadata"`,
  `"stats"`, empty `"partitionValues"`) and the `{"add":`/`{"remove":`
  line tags;
- vectorized span extraction and int parse: string spans resolve
  their closing quote through a quote-rank scatter, numerics parse
  with an unrolled Horner loop in scoped-x64 int64.

A line is SIMPLE when its depth-2 colon census is fully explained by
matched known keys, it has no depth>=3 colons (nested deletionVector /
tags / non-empty partitionValues objects), and every matched numeric/
boolean value validates. Anything else — and any window whose lines
fail the structural balance checks (odd quote count, unbalanced or
negative brace depth) — routes the WHOLE window back to the host
scanner, preserving digest parity by construction: the device route
only ever answers for content it parsed exactly.

Per-line quantities are never reduced by line over the byte lane (a
`segment_sum` / `segment_min` keyed by `line_id` lowers on the TPU to a
scatter of one update a byte). Each is an inclusive chunked scan over
the lane (`ops/scans.py`) read at the line's ends by a gather as long as
the line lanes: a count is `cum[last] - cum[line_start - 1]`, the
closing depth `depth[line_end]`, a line tag its match mask read at
`line_start`, a key's first match the reversed running minimum of the
match positions read at `line_start`, and its repeat the same lane read
once more behind that match.

Per-line result lanes come back as three dense blocks (int64 values,
int32 spans, packed flags), so the D2H cost is O(lines), not O(bytes).
Escaped string spans (backslashes in paths or stats) are flagged and
unescaped host-side by the caller (`replay/device_parse.py`).

Windows at or beyond 2 GiB are rejected up front (`window_eligible`):
every span lane is int32, and a >=2^31 byte offset would wrap.
"""

from __future__ import annotations

import functools

import numpy as np

from delta_tpu import obs

# Window spans are int32: a window must keep every byte offset below
# 2^31. Callers split larger buffers (replay/device_parse.py windows at
# DELTA_TPU_DEVICE_PARSE_WINDOW, default 64 MiB) long before this trips.
MAX_WINDOW_BYTES = (1 << 31) - 1

_PAT_ADD = b'{"add":{'
_PAT_REMOVE = b'{"remove":{'

# Known depth-2 keys of simple add/remove actions. Order is the lane
# order of the kernel outputs. kind: str -> quoted span; int -> int64
# numeric; bool -> true/false literal; empty -> literal '{}' value.
KEY_PATTERNS = (
    ("path", b'"path":"', "str"),
    ("stats", b'"stats":"', "str"),
    ("size", b'"size":', "int"),
    ("mod_time", b'"modificationTime":', "int"),
    ("del_ts", b'"deletionTimestamp":', "int"),
    ("data_change", b'"dataChange":', "bool"),
    ("ext_meta", b'"extendedFileMetadata":', "bool"),
    ("pv_empty", b'"partitionValues":{}', "empty"),
)
_STR_KEYS = tuple(i for i, p in enumerate(KEY_PATTERNS) if p[2] == "str")
_INT_KEYS = tuple(i for i, p in enumerate(KEY_PATTERNS) if p[2] == "int")
_BOOL_KEYS = tuple(i for i, p in enumerate(KEY_PATTERNS) if p[2] == "bool")

_TAIL_PAD = 32  # > longest pattern; keeps shifted compares off the edge
_MAX_INT_DIGITS = 18  # int64-safe; 19+ digit values fall back to host

# flag-lane order in the packed bool block
FLAG_NAMES = (
    "is_add", "is_remove", "complex",
    "path_esc", "stats_esc", "stats_present",
    "size_present", "mod_time_present", "del_ts_present",
    "data_change_present", "data_change_val",
    "ext_meta_present", "ext_meta_val",
    "pv_present",
)
# int32 span-lane order
SPAN_NAMES = ("line_start", "line_end",
              "path_start", "path_end", "stats_start", "stats_end")
# int64 value-lane order
VAL_NAMES = ("size_val", "mod_time_val", "del_ts_val")


def window_eligible(nbytes: int) -> bool:
    """int32-span guard: offsets in a window must fit in int32."""
    return 0 < nbytes < MAX_WINDOW_BYTES


def _use_device_classes() -> bool:
    """Run the byte-class stage as a real Pallas kernel only on TPU;
    interpret-mode Pallas on CPU costs more than the fused jnp
    compares it replaces."""
    import jax

    return jax.default_backend() == "tpu"


@functools.lru_cache(maxsize=32)
def _parse_fn_cached(n_pad: int, l_pad: int, pallas_classes: bool):
    """jit'd whole-window field extraction.

    Input: `bx` [n_pad + _TAIL_PAD] uint8 (real bytes then 0x20
    padding), `n_lines` int32 scalar (real line count). Output:
    (vals [3, l_pad] int64, spans [6, l_pad] int32,
     flags [len(FLAG_NAMES), l_pad] bool, window_ok scalar bool).
    """
    import jax
    import jax.numpy as jnp

    from delta_tpu.ops.scans import cummax_1d, cummin_1d, cumsum_1d

    n = n_pad
    big = jnp.int32(n)

    def shift_in(m):
        """Previous-byte view of a mask (False shifted in at pos 0)."""
        return jnp.concatenate([jnp.zeros(1, m.dtype), m[:-1]])

    def gather32(arr, idx, limit):
        return arr[jnp.clip(idx, 0, limit)]

    @obs.program("json_parse.window")
    def kernel(bx, n_lines):
        with jax.named_scope("parse.classes"):
            b = bx[:n]
            pos = jnp.arange(n, dtype=jnp.int32)
            if pallas_classes:
                from delta_tpu.ops.pallas_kernels import byte_class_tiled

                cls = byte_class_tiled(b)
                nl = (cls & 1) != 0
                quote = (cls & 2) != 0
                bs = (cls & 4) != 0
                colon = (cls & 8) != 0
                lb = (cls & 16) != 0
                rb = (cls & 32) != 0
            else:
                nl = b == 10
                quote = b == 34
                bs = b == 92
                colon = b == 58
                lb = b == 123
                rb = b == 125

        with jax.named_scope("parse.lines"):
            nli = nl.astype(jnp.int32)
            nl_rank = cumsum_1d(nli)         # inclusive newline rank
            line_id = nl_rank - nli          # line containing each byte
            drop = jnp.int32(l_pad)          # OOB segment sentinel
            line_start = (jnp.zeros(l_pad, jnp.int32)
                          .at[jnp.where(nl, nl_rank, drop)]
                          .set(pos + 1, mode="drop"))
            line_end = (jnp.full(l_pad, n, jnp.int32)
                        .at[jnp.where(nl, nl_rank - 1, drop)]
                        .set(pos, mode="drop"))
            # lines that hold a byte: line l is [line_start, last]; the
            # tail line has no newline, lines past it are empty
            lane = jnp.arange(l_pad, dtype=jnp.int32)
            n_nl = nl_rank[n - 1]
            nonempty = (lane <= n_nl) & (line_start < n)
            last = jnp.minimum(line_end, n - 1)

        with jax.named_scope("parse.quotes"):
            # escape initiators: a backslash at even offset within its run
            run_start = bs & ~shift_in(bs)
            last_rs = cummax_1d(jnp.where(run_start, pos, jnp.int32(-1)))
            initiator = bs & (((pos - last_rs) & 1) == 0)
            uq = quote & ~shift_in(initiator)  # structurally active quote
            uqi = uq.astype(jnp.int32)
            q_cum = cumsum_1d(uqi)
            outside = ((q_cum - uqi) & 1) == 0  # even quote parity before
            # rank -> position of each active quote (closing-quote lookup)
            pos_by_rank = (jnp.full(n + 1, n, jnp.int32)
                           .at[jnp.where(uq, q_cum - 1, big)]
                           .set(pos, mode="drop"))
            bs_cum = cumsum_1d(bs.astype(jnp.int32))

        def line_sum(cum):
            """Per-line sum of a mask, from its inclusive prefix sum."""
            before = jnp.where(line_start > 0,
                               gather32(cum, line_start - 1, n - 1), 0)
            return jnp.where(nonempty, gather32(cum, last, n - 1) - before, 0)

        with jax.named_scope("parse.depth"):
            s_colon = colon & outside
            depth = cumsum_1d((lb & outside).astype(jnp.int32)
                              - (rb & outside).astype(jnp.int32))
            c1 = s_colon & (depth == 1)
            c2 = s_colon & (depth == 2)
            c3 = s_colon & (depth >= 3)
            # one scan for two counts: a line has fewer than 2^31 bytes,
            # so n_c1 + 2 * n_c3 < 2^32 cannot wrap round to 1, and it is
            # 1 only where n_c1 == 1 and n_c3 == 0
            c13_ok = line_sum(cumsum_1d(
                c1.astype(jnp.int32) + 2 * c3.astype(jnp.int32))) == 1
            n_c2 = line_sum(cumsum_1d(c2.astype(jnp.int32)))
            n_quotes = line_sum(q_cum)
            depth_end = jnp.where(lane < n_nl,
                                  gather32(depth, line_end, n - 1), 0)
            # "some line's depth dips below 0" needs no per-line minimum
            neg_depth = jnp.any((depth < 0) & (line_id < n_lines))

        def gather8(idx):
            return bx[jnp.clip(idx, 0, n + _TAIL_PAD - 1)]

        with jax.named_scope("parse.keys"):
            def match(pat):
                acc = jnp.ones(n, bool)
                for k, ch in enumerate(pat):
                    acc = acc & (bx[k:k + n] == np.uint8(ch))
                return acc

            # a line tag stands at the line's first byte or nowhere
            first_byte = jnp.minimum(line_start, n - 1)
            is_add = nonempty & match(_PAT_ADD)[first_byte]
            is_rem = nonempty & match(_PAT_REMOVE)[first_byte]
            filerow = is_add | is_rem

            # per key: `one` (exactly one match in the line), `dup` (two
            # or more) and `mpos`, the first match: n where the line has
            # none, int32 max where the line is empty (what a minimum
            # over no byte gives)
            one, dup_key, mpos = [], [], []
            for _name, pat, _kind in KEY_PATTERNS:
                m = match(pat) & uq & outside & (depth == 2)
                nxt = cummin_1d(jnp.where(m, pos, big), reverse=True)
                first = gather32(nxt, line_start, n - 1)
                found = nonempty & (first <= last)
                second = jnp.where(first < n - 1,
                                   gather32(nxt, first + 1, n - 1), big)
                twice = found & (second <= last)
                one.append(found & ~twice)
                dup_key.append(twice)
                mpos.append(jnp.where(
                    nonempty, jnp.where(found, first, big),
                    jnp.iinfo(jnp.int32).max))

        with jax.named_scope("parse.strings"):
            # string spans: [open_quote + 1, closing quote)
            span_start, span_end, span_esc, span_bad = {}, {}, {}, {}
            for i in _STR_KEYS:
                name, pat, _ = KEY_PATTERNS[i]
                present = one[i]
                o = mpos[i] + np.int32(len(pat) - 1)   # value's opening quote
                rank = gather32(q_cum, o, n - 1)
                close = gather32(pos_by_rank, rank, n)
                start = o + 1
                nbs = (gather32(bs_cum, close - 1, n - 1)
                       - gather32(bs_cum, start - 1, n - 1))
                span_start[name] = jnp.where(present, start, 0)
                span_end[name] = jnp.where(present, close, 0)
                span_esc[name] = present & (nbs > 0)
                span_bad[name] = present & ((close >= line_end)
                                            | (close <= o))

        with jax.named_scope("parse.ints"):
            # numerics: unrolled Horner over at most _MAX_INT_DIGITS digits
            num_val, num_present, num_bad = {}, {}, {}
            for i in _INT_KEYS:
                name, pat, _ = KEY_PATTERNS[i]
                present = one[i]
                vs = mpos[i] + np.int32(len(pat))
                negm = gather8(vs) == np.uint8(45)
                base = vs + negm.astype(jnp.int32)
                val = jnp.zeros(l_pad, jnp.int64)
                active = jnp.ones(l_pad, bool)
                term_ok = jnp.zeros(l_pad, bool)
                ndig = jnp.zeros(l_pad, jnp.int32)
                for j in range(_MAX_INT_DIGITS + 1):
                    ch = gather8(base + np.int32(j))
                    is_d = (ch >= np.uint8(48)) & (ch <= np.uint8(57))
                    take = active & is_d
                    val = jnp.where(take,
                                    val * 10 + (ch - np.uint8(48))
                                    .astype(jnp.int64), val)
                    ndig = ndig + take.astype(jnp.int32)
                    stop = active & ~is_d
                    term_ok = jnp.where(
                        stop, (ch == np.uint8(44)) | (ch == np.uint8(125)),
                        term_ok)
                    active = active & is_d
                num_val[name] = jnp.where(negm, -val, val)
                num_present[name] = present
                # still-active after the unroll = too many digits for int64
                num_bad[name] = present & (active | (ndig < 1) | ~term_ok)

        with jax.named_scope("parse.flags"):
            bool_val, bool_present, bool_bad = {}, {}, {}
            for i in _BOOL_KEYS:
                name, pat, _ = KEY_PATTERNS[i]
                present = one[i]
                ch = gather8(mpos[i] + np.int32(len(pat)))
                bool_val[name] = ch == np.uint8(116)   # 't'
                bool_present[name] = present
                bool_bad[name] = present & (ch != np.uint8(116)) \
                    & (ch != np.uint8(102))            # nor 'f'

            # the key masks are disjoint by position, so a line's match
            # count is the sum of its keys' counts; where a key repeats
            # the line is complex whatever that sum, elsewhere each count
            # is 0 or 1
            matched = jnp.zeros(l_pad, jnp.int32)
            dup = jnp.zeros(l_pad, bool)
            for o, d in zip(one, dup_key):
                matched = matched + o.astype(jnp.int32)
                dup = dup | d
            tail_ch = gather8(line_end - 1)
            any_bad = (span_bad["path"] | span_bad["stats"]
                       | num_bad["size"] | num_bad["mod_time"]
                       | num_bad["del_ts"]
                       | bool_bad["data_change"] | bool_bad["ext_meta"])
            complex_line = filerow & (
                ~c13_ok | (n_c2 != matched) | dup
                | ~one[0]                          # path is mandatory
                | (tail_ch != np.uint8(125))       # line must close with '}'
                | any_bad)

            valid_line = lane < n_lines
            bal_bad = valid_line & (((n_quotes & 1) != 0)
                                    | (depth_end != 0))
            window_ok = ~(jnp.any(bal_bad) | neg_depth)

            vals = jnp.stack([num_val["size"], num_val["mod_time"],
                              num_val["del_ts"]])
            spans = jnp.stack([line_start, line_end,
                               span_start["path"], span_end["path"],
                               span_start["stats"], span_end["stats"]])
            flags = jnp.stack([
                is_add, is_rem, complex_line,
                span_esc["path"], span_esc["stats"],
                one[1],
                num_present["size"], num_present["mod_time"],
                num_present["del_ts"],
                bool_present["data_change"], bool_val["data_change"],
                bool_present["ext_meta"], bool_val["ext_meta"],
                one[7],
            ])
            return vals, spans, flags, window_ok

    return jax.jit(kernel)


def parse_window_fields(window: np.ndarray, n_lines: int, device=None):
    """Run the field-extraction kernel over one newline-terminated
    uint8 window. Returns a dict of per-line numpy lanes (keys:
    VAL_NAMES + SPAN_NAMES + FLAG_NAMES, each length `n_lines`) or
    None when the window failed the structural balance checks.

    One H2D copy: the padded uint8 lane (`json-parse-window` budget
    entry). D2H is three dense per-line blocks.
    """
    import jax

    from delta_tpu.ops.replay import pad_bucket
    from delta_tpu.ops.stats import _x64

    n = int(window.shape[0])
    if not window_eligible(n):
        return None
    from delta_tpu.ops.pallas_kernels import _BYTE_TILE

    # every bucket is a whole number of byte-class tiles, so the class
    # stage has one implementation per backend
    n_pad = pad_bucket(n, min_bucket=_BYTE_TILE)
    l_pad = pad_bucket(n_lines + 1)
    # 0x20 padding: joins the (discarded) tail line, matches no pattern
    lane_bytes = np.full(n_pad + _TAIL_PAD, 0x20, np.uint8)
    lane_bytes[:n] = window
    pallas_ok = _use_device_classes()
    fn = _parse_fn_cached(n_pad, l_pad, pallas_ok)
    with obs.device_dispatch("json_parse.window",
                             key=(n_pad, l_pad, pallas_ok),
                             budget="json-parse-window",
                             units=lane_bytes.shape[0],
                             gate="parse") as dd, _x64():
        dd.h2d("lane_bytes", lane_bytes)
        vals, spans, flags, window_ok = fn(
            jax.device_put(lane_bytes, device), np.int32(n_lines))
        with obs.span("parse.wait", bytes=n, rows=n_lines):
            if not bool(window_ok):
                dd.set(window_ok=False)
                return None
            vals = dd.d2h("vals", np.asarray(vals))[:, :n_lines]
            spans = dd.d2h("spans", np.asarray(spans))[:, :n_lines]
            flags = dd.d2h("flags", np.asarray(flags))[:, :n_lines]
    out = {}
    for i, name in enumerate(VAL_NAMES):
        out[name] = vals[i]
    for i, name in enumerate(SPAN_NAMES):
        out[name] = spans[i]
    for i, name in enumerate(FLAG_NAMES):
        out[name] = flags[i]
    return out
