"""Device JSON action parse + device DV decode: parity vs the host
routes, fallback behavior, and the bit-width guards that ride along.

Everything runs with JAX on CPU (the kernels' jnp twin); the Pallas
byte-class path is exercised on TPU only. Parity is asserted against
the exact same assembly the C++ scanner / generic parser produce, so a
green run here means the device route is digest-identical by
construction.
"""

import functools
import json
import struct

import numpy as np
import pyarrow as pa
import pytest


# real Delta logs are compact; the device kernel's patterns key on the
# compact form, and anything else routes the window to the host parser
_dumps = functools.partial(json.dumps, separators=(",", ":"))


# --------------------------------------------------------------- helpers ----

def _mk_log(tmp_path, commits):
    """Write `commits` (list of list-of-json-lines) as a _delta_log dir."""
    log = tmp_path / "_delta_log"
    log.mkdir(exist_ok=True)
    for v, lines in enumerate(commits):
        (log / f"{v:020d}.json").write_text("\n".join(lines) + "\n")
    return log


def _columnarize(tmp_path, monkeypatch, route):
    """Columnarize tmp_path's log with the parse route forced to
    `route` ('force' = device, '0' = host)."""
    from delta_tpu.engine.host import HostEngine
    from delta_tpu.log.segment import build_log_segment
    from delta_tpu.replay.columnar import columnarize_log_segment

    monkeypatch.setenv("DELTA_TPU_DEVICE_PARSE", route)
    eng = HostEngine()
    seg = build_log_segment(eng.fs, str(tmp_path / "_delta_log"))
    return columnarize_log_segment(eng, seg)


def _norm(t):
    idx = pa.compute.sort_indices(
        t, sort_keys=[("version", "ascending"), ("order", "ascending")])
    return t.take(idx)


_PROTO = '{"protocol":{"minReaderVersion":1,"minWriterVersion":2}}'
_META = ('{"metaData":{"id":"m","format":{"provider":"parquet",'
         '"options":{}},"schemaString":"{}","partitionColumns":[],'
         '"configuration":{}}}')


def _add(path, size=1, mod=1, dc=True, stats=None, extra=None):
    a = {"path": path, "partitionValues": {}, "size": size,
         "modificationTime": mod, "dataChange": dc}
    if stats is not None:
        a["stats"] = stats
    if extra:
        a.update(extra)
    return _dumps({"add": a})


def _buffer(commits):
    """list-of-list-of-lines -> (buf, starts[n+1], versions) as
    `_read_commits_buffer` would produce them."""
    blobs = [("\n".join(lines) + "\n").encode() for lines in commits]
    starts = np.zeros(len(blobs) + 1, np.int64)
    np.cumsum([len(b) for b in blobs], out=starts[1:])
    return b"".join(blobs), starts, np.arange(len(blobs), dtype=np.int64)


# ------------------------------------------------- columnarize parity -------

def test_device_parity_full_corpus(tmp_path, monkeypatch):
    """Device route must be row-identical to the host route on a corpus
    covering escapes, unicode, nested stats JSON, missing optionals,
    booleans both ways, and control lines."""
    commits = [
        [_PROTO, _META,
         _add("plain.parquet", size=10, mod=100,
              stats='{"numRecords":5,"minValues":{"x":1}}'),
         _dumps({"commitInfo": {"operation": "WRITE", "n": 0}})],
        # escaped quotes + backslashes + solidus in the path
        [_add('esc\\"q\\\\b\\/s.parquet', size=2, mod=2),
         # unicode escapes incl. a surrogate pair
         _add('caf\\u00e9\\ud83d\\ude00.parquet', size=3, mod=3)],
        # stats is JSON-in-a-string with nested braces/quotes
        [_add("nested.parquet", size=4, mod=4,
              stats=_dumps({"numRecords": 2,
                            "minValues": {"s": 'a"b{c}'},
                            "nullCount": {"s": 0}}))],
        # missing optionals: no stats, dataChange=false, a remove
        [_add("nostats.parquet", size=5, mod=5, dc=False),
         _dumps({"remove": {"path": "plain.parquet",
                                "deletionTimestamp": 999,
                                "dataChange": True,
                                "extendedFileMetadata": False}})],
        # remove without optional fields at all
        [_dumps({"remove": {"path": "nostats.parquet",
                                "dataChange": False}}),
         _dumps({"commitInfo": {"operation": "DELETE"}})],
    ]
    _mk_log(tmp_path, commits)
    from delta_tpu import obs

    windows_before = obs.counter("parse.device_windows").value
    col_dev = _columnarize(tmp_path, monkeypatch, "force")
    # the corpus must actually take the device route — a silent host
    # fallback would make this parity test vacuous
    assert obs.counter("parse.device_windows").value > windows_before
    col_host = _columnarize(tmp_path, monkeypatch, "0")

    td, th = _norm(col_dev.file_actions_complete()), _norm(
        col_host.file_actions_complete())
    assert td.num_rows == th.num_rows
    for name in td.column_names:
        assert td.column(name).to_pylist() == th.column(name).to_pylist(), name
    assert col_dev.protocol == col_host.protocol
    assert col_dev.metadata == col_host.metadata
    assert col_dev.commit_infos.keys() == col_host.commit_infos.keys()


def test_device_parity_percent_encoded_and_long_ints(tmp_path, monkeypatch):
    commits = [
        [_PROTO, _META,
         _add("a%20b%2Fc.parquet", size=2**53 + 111, mod=1700000000123),
         _dumps({"remove": {"path": "a%20b%2Fc.parquet",
                                "deletionTimestamp": 2**53 + 7,
                                "dataChange": True}})],
    ]
    _mk_log(tmp_path, commits)
    col_dev = _columnarize(tmp_path, monkeypatch, "force")
    col_host = _columnarize(tmp_path, monkeypatch, "0")
    td, th = _norm(col_dev.file_actions_complete()), _norm(
        col_host.file_actions_complete())
    for name in ("path", "size", "modification_time", "deletion_timestamp"):
        assert td.column(name).to_pylist() == th.column(name).to_pylist(), name


# --------------------------------------------- direct window-level API ------

def test_parse_commits_device_basic():
    from delta_tpu.replay.device_parse import parse_commits_device

    buf, starts, versions = _buffer([
        [_add("x.parquet", size=7, mod=70, stats='{"numRecords":1}')],
        [_dumps({"remove": {"path": "x.parquet",
                                "deletionTimestamp": 5,
                                "dataChange": True}})],
    ])
    out = parse_commits_device(buf, starts, versions)
    assert out is not None
    table = out[0]
    assert table.num_rows == 2
    assert table.column("path").to_pylist() == ["x.parquet", "x.parquet"]
    assert table.column("is_add").to_pylist() == [True, False]
    assert table.column("size").to_pylist() == [7, None]
    assert table.column("deletion_timestamp").to_pylist() == [None, 5]


def test_dv_line_falls_back_whole_window():
    """A deletionVector sub-object makes the line complex; digest parity
    requires the WHOLE window to take the host route (None here)."""
    from delta_tpu import obs
    from delta_tpu.replay.device_parse import parse_commits_device

    before = obs.counter("parse.device_fallbacks").value
    buf, starts, versions = _buffer([
        [_add("p.parquet"),
         _add("q.parquet", extra={"deletionVector": {
             "storageType": "u", "pathOrInlineDv": "ab", "offset": 1,
             "sizeInBytes": 40, "cardinality": 2}})],
    ])
    assert parse_commits_device(buf, starts, versions) is None
    assert obs.counter("parse.device_fallbacks").value == before + 1


def test_corrupt_window_falls_back():
    from delta_tpu.replay.device_parse import parse_commits_device

    buf, starts, versions = _buffer([['{"add":{"path": broken']])
    assert parse_commits_device(buf, starts, versions) is None


def test_whitespace_file_action_falls_back():
    """A legal-but-spaced add line doesn't match the compact-form
    patterns; treating it as a control line would silently drop a file
    action, so the window must route to the host parser instead."""
    from delta_tpu.replay.device_parse import parse_commits_device

    spaced = json.dumps(
        {"add": {"path": "s.parquet", "partitionValues": {}, "size": 1,
                 "modificationTime": 1, "dataChange": True}})
    assert ": " in spaced  # default separators keep the space
    buf, starts, versions = _buffer([[_add("ok.parquet"), spaced]])
    assert parse_commits_device(buf, starts, versions) is None


def test_window_eligible_2gb_guard():
    from delta_tpu.ops.json_parse import MAX_WINDOW_BYTES, window_eligible

    assert window_eligible(1)
    assert window_eligible(MAX_WINDOW_BYTES - 1)
    assert not window_eligible(MAX_WINDOW_BYTES)  # offsets must fit int32
    assert not window_eligible(1 << 31)
    assert not window_eligible(0)


def test_parse_route_env_and_economics(monkeypatch):
    from delta_tpu.parallel import gate

    monkeypatch.delenv("DELTA_TPU_DEVICE_PARSE", raising=False)
    # engine not opted in -> host regardless of size
    assert gate.parse_route(1 << 30, engine_enabled=False) == "host"
    # env force outranks everything
    monkeypatch.setenv("DELTA_TPU_DEVICE_PARSE", "force")
    assert gate.parse_route(0, engine_enabled=False) == "device"
    monkeypatch.setenv("DELTA_TPU_DEVICE_PARSE", "off")
    assert gate.parse_route(1 << 30, engine_enabled=True) == "host"


# ------------------------------------------- scan-form bit parity -----------
# The kernel forms every per-line quantity from a prefix scan read at the
# line's ends. The reference below is the formulation it replaced, kept
# here only: one `segment_sum` / `segment_min` by `line_id` over the byte
# lane per quantity. Every output lane, padded lines included, and
# `window_ok` must come out the same.

@functools.lru_cache(maxsize=None)
def _segment_reduce_reference(n_pad, l_pad):
    import jax
    import jax.numpy as jnp

    from delta_tpu.ops import json_parse as jp

    n, big = n_pad, jnp.int32(n_pad)

    def shift_in(m):
        return jnp.concatenate([jnp.zeros(1, m.dtype), m[:-1]])

    def reference(bx, n_lines):
        b = bx[:n]
        pos = jnp.arange(n, dtype=jnp.int32)
        nl, quote, bs = b == 10, b == 34, b == 92
        colon, lb, rb = b == 58, b == 123, b == 125

        nli = nl.astype(jnp.int32)
        nl_rank = jnp.cumsum(nli)
        line_id = nl_rank - nli
        drop = jnp.int32(l_pad)
        line_start = (jnp.zeros(l_pad, jnp.int32)
                      .at[jnp.where(nl, nl_rank, drop)]
                      .set(pos + 1, mode="drop"))
        line_end = (jnp.full(l_pad, n, jnp.int32)
                    .at[jnp.where(nl, nl_rank - 1, drop)]
                    .set(pos, mode="drop"))

        run_start = bs & ~shift_in(bs)
        last_rs = jax.lax.cummax(jnp.where(run_start, pos, jnp.int32(-1)))
        initiator = bs & (((pos - last_rs) & 1) == 0)
        uq = quote & ~shift_in(initiator)
        uqi = uq.astype(jnp.int32)
        q_cum = jnp.cumsum(uqi)
        outside = ((q_cum - uqi) & 1) == 0
        pos_by_rank = (jnp.full(n + 1, n, jnp.int32)
                       .at[jnp.where(uq, q_cum - 1, big)]
                       .set(pos, mode="drop"))
        bs_cum = jnp.cumsum(bs.astype(jnp.int32))

        s_colon = colon & outside
        depth = jnp.cumsum((lb & outside).astype(jnp.int32)
                           - (rb & outside).astype(jnp.int32))

        def seg_sum(m):
            return jax.ops.segment_sum(m.astype(jnp.int32), line_id,
                                       num_segments=l_pad)

        n_c1 = seg_sum(s_colon & (depth == 1))
        n_c2 = seg_sum(s_colon & (depth == 2))
        n_c3 = seg_sum(s_colon & (depth >= 3))
        n_quotes = seg_sum(uqi)
        depth_end = (jnp.zeros(l_pad, jnp.int32)
                     .at[jnp.where(nl, line_id, drop)]
                     .set(depth, mode="drop"))
        depth_min = jax.ops.segment_min(depth, line_id, num_segments=l_pad)

        at_ls = shift_in(nl).at[0].set(True)

        def match(pat):
            acc = jnp.ones(n, bool)
            for k, ch in enumerate(pat):
                acc = acc & (bx[k:k + n] == np.uint8(ch))
            return acc

        is_add = seg_sum(match(jp._PAT_ADD) & at_ls) > 0
        is_rem = seg_sum(match(jp._PAT_REMOVE) & at_ls) > 0
        filerow = is_add | is_rem
        counts, mpos = [], []
        for _name, pat, _kind in jp.KEY_PATTERNS:
            m = match(pat) & uq & outside & (depth == 2)
            counts.append(seg_sum(m))
            mpos.append(jax.ops.segment_min(
                jnp.where(m, pos, big), line_id, num_segments=l_pad))

        def gather8(idx):
            return bx[jnp.clip(idx, 0, n + jp._TAIL_PAD - 1)]

        def gather32(arr, idx, limit):
            return arr[jnp.clip(idx, 0, limit)]

        span_start, span_end, span_esc, span_bad = {}, {}, {}, {}
        for i in jp._STR_KEYS:
            name, pat, _ = jp.KEY_PATTERNS[i]
            present = counts[i] == 1
            o = mpos[i] + np.int32(len(pat) - 1)
            close = gather32(pos_by_rank, gather32(q_cum, o, n - 1), n)
            start = o + 1
            nbs = (gather32(bs_cum, close - 1, n - 1)
                   - gather32(bs_cum, start - 1, n - 1))
            span_start[name] = jnp.where(present, start, 0)
            span_end[name] = jnp.where(present, close, 0)
            span_esc[name] = present & (nbs > 0)
            span_bad[name] = present & ((close >= line_end) | (close <= o))

        num_val, num_bad = {}, {}
        for i in jp._INT_KEYS:
            name, pat, _ = jp.KEY_PATTERNS[i]
            vs = mpos[i] + np.int32(len(pat))
            negm = gather8(vs) == np.uint8(45)
            base = vs + negm.astype(jnp.int32)
            val = jnp.zeros(l_pad, jnp.int64)
            active = jnp.ones(l_pad, bool)
            term_ok = jnp.zeros(l_pad, bool)
            ndig = jnp.zeros(l_pad, jnp.int32)
            for j in range(jp._MAX_INT_DIGITS + 1):
                ch = gather8(base + np.int32(j))
                is_d = (ch >= np.uint8(48)) & (ch <= np.uint8(57))
                take = active & is_d
                val = jnp.where(
                    take, val * 10 + (ch - np.uint8(48)).astype(jnp.int64),
                    val)
                ndig = ndig + take.astype(jnp.int32)
                term_ok = jnp.where(
                    active & ~is_d,
                    (ch == np.uint8(44)) | (ch == np.uint8(125)), term_ok)
                active = active & is_d
            num_val[name] = jnp.where(negm, -val, val)
            num_bad[name] = (counts[i] == 1) & (active | (ndig < 1)
                                                | ~term_ok)

        bool_val, bool_bad = {}, {}
        for i in jp._BOOL_KEYS:
            name, pat, _ = jp.KEY_PATTERNS[i]
            ch = gather8(mpos[i] + np.int32(len(pat)))
            bool_val[name] = ch == np.uint8(116)
            bool_bad[name] = ((counts[i] == 1) & (ch != np.uint8(116))
                              & (ch != np.uint8(102)))

        matched = sum(counts[1:], counts[0])
        dup = functools.reduce(jnp.logical_or, [c > 1 for c in counts])
        any_bad = functools.reduce(
            jnp.logical_or,
            [*span_bad.values(), *num_bad.values(), *bool_bad.values()])
        complex_line = filerow & (
            (n_c1 != 1) | (n_c2 != matched) | (n_c3 > 0) | dup
            | (counts[0] != 1)
            | (gather8(line_end - 1) != np.uint8(125)) | any_bad)
        valid_line = jnp.arange(l_pad, dtype=jnp.int32) < n_lines
        window_ok = ~jnp.any(valid_line & (
            ((n_quotes & 1) != 0) | (depth_end != 0) | (depth_min < 0)))

        present = {p[0]: counts[i] == 1
                   for i, p in enumerate(jp.KEY_PATTERNS)}
        vals = jnp.stack([num_val[k] for k in ("size", "mod_time", "del_ts")])
        spans = jnp.stack([line_start, line_end,
                           span_start["path"], span_end["path"],
                           span_start["stats"], span_end["stats"]])
        flags = jnp.stack([
            is_add, is_rem, complex_line,
            span_esc["path"], span_esc["stats"], present["stats"],
            present["size"], present["mod_time"], present["del_ts"],
            present["data_change"], bool_val["data_change"],
            present["ext_meta"], bool_val["ext_meta"],
            present["pv_empty"]])
        return vals, spans, flags, window_ok

    return jax.jit(reference)


_RM = _dumps({"remove": {"path": "r.parquet", "deletionTimestamp": 9,
                         "dataChange": True}})
_INFO = '{"commitInfo":{"timestamp":1,"operation":"WRITE"}}'


def _fuzz_window(seed):
    """Lines of key/value atoms in any order and number, one atom in
    thirty structural noise, so that counts, first matches, quotes and
    depth go wrong in every way a line can."""
    rng = np.random.default_rng(seed)
    pairs = ['"path":"p%d"', '"stats":"s%d"', '"size":%d',
             '"modificationTime":%d', '"deletionTimestamp":-%d',
             '"dataChange":true', '"dataChange":false',
             '"extendedFileMetadata":true', '"partitionValues":{}',
             '"tags":{"t":%d}', '"size":"%d"', '"dataChange":%d', '"foo":%d',
             '{"k":%d}']
    noise = ["{", "}", '"', ":", "\\", '\\"', "\n", "}}", ""]
    lines = []
    for _ in range(120):
        atoms = [(noise[rng.integers(len(noise))] if rng.random() < 0.03
                  else pairs[rng.integers(len(pairs))].replace(
                      "%d", str(rng.integers(1000))))
                 for _ in range(rng.integers(0, 9))]
        head = ('{"add":{', '{"remove":{', '{"txn":{')[rng.integers(3)]
        lines.append(head + ",".join(atoms) + "}}")
    return "\n".join(lines) + "\n"


def _repeat(key_value, times, then=""):
    """One add line whose `key_value` stands `times` times, then `then`:
    a key met once *after* the repeats must still read as present."""
    return ('{"add":{"path":"a.parquet",' + ",".join([key_value] * times)
            + then + "}}")


_PARITY_WINDOWS = {
    "simple_lines": lambda: "\n".join(
        [_PROTO, _META, _add("a.parquet", size=12, mod=34,
                             stats='{"numRecords":5}'), _RM, _INFO]) + "\n",
    "empty_lines": lambda: "\n\n" + _add("a.parquet") + "\n\n\n" + _RM
    + "\n\n",
    "no_match_lines": lambda: "\n".join([_PROTO, _INFO, _META, "{}", "x"])
    + "\n",
    "key_twice": lambda: _repeat(
        '"size":1', 1, ',"size":2,"modificationTime":5') + "\n" + _RM + "\n",
    "key_16_times": lambda: _add("b.parquet") + "\n" + _repeat(
        '"dataChange":true', 15, ',"dataChange":false,"size":-44') + "\n",
    "key_256_times": lambda: _repeat(
        '"stats":"s"', 256, ',"deletionTimestamp":8') + "\n" + _RM + "\n",
    "key_65536_times": lambda: _RM + "\n" + _repeat(
        '"size":3', 65536, ',"extendedFileMetadata":false') + "\n" + _RM
    + "\n",
    # only the colon census by depth makes these lines complex
    "colons_300_at_depth_3": lambda: '{"add":{"path":"c.parquet",{'
    + ",".join(['"k":1'] * 300) + '},"size":2}}\n' + _RM + "\n",
    "one_colon_at_depth_3": lambda:
        '{"add":{"path":"c.parquet",{"k":1},"size":2}}\n' + _RM + "\n",
    "unknown_key_at_depth_2": lambda:
        '{"add":{"path":"c.parquet","foo":1,"size":2}}\n' + _RM + "\n",
    "two_colons_at_depth_1": lambda:
        '{"add":{"path":"c.parquet","size":2},"x":{}}\n' + _RM + "\n",
    "control_line_with_path_key": lambda:
        '{"commitInfo":{"path":"p","size":3,"operation":"WRITE"}}\n'
        + _add("d.parquet") + "\n",
    "odd_quotes": lambda: _add("e.parquet") + "\n" + '{"add":{"path":"q}}'
    + "\n" + _RM + "\n",
    "negative_depth": lambda: _add("f.parquet") + "\n}}{{\n" + _RM + "\n",
    "match_in_first_byte_of_a_line": lambda:
        '{"add":{\n"size":7,"path":"g"}}\n' + _RM + "\n",
    "match_in_last_bytes_of_a_line": lambda:
        '{"add":{"path":"h","size":\n4}}\n{"add":{"path":"i",'
        '"partitionValues":{}\n}}\n',
    "match_in_last_bytes_of_the_window": lambda:
        _RM + "\n" + '{"add":{"path":"j","modificationTime":',
    "tail_line_without_newline": lambda: _add("k.parquet") + "\n" + _RM,
    "window_fills_the_lane": lambda: (
        (_add("l.parquet") + "\n") * 400)[:16383] + "}",
    # empty and padded lines read their values at byte 0 of the window
    "empty_lines_after_a_digit": lambda: "7\n\n" + _add("n.parquet") + "\n",
    "empty_lines_after_a_t": lambda: "t\n\n" + _RM + "\n\n",
    "newline_fills_the_lane": lambda: "7" + (
        (_add("o.parquet") + "\n") * 400)[:16382] + "\n",
    "fewer_lines_than_the_window_holds": lambda: (
        _add("p.parquet") + "\n" + _RM + "\n}}{{\n" + '{"add":{"q\n', 2),
    "escapes_and_percent": lambda: _add(
        "m%20n\\\\.parquet", stats='{"a":"\\"x\\""}') + "\n" + _RM + "\n",
    **{f"fuzz_{seed}": functools.partial(_fuzz_window, seed)
       for seed in range(6)},
}


@pytest.mark.parametrize("case", _PARITY_WINDOWS)
def test_scan_form_matches_the_segment_reduces_bit_for_bit(case):
    import jax

    from delta_tpu.ops import json_parse as jp
    from delta_tpu.ops.replay import pad_bucket

    window = _PARITY_WINDOWS[case]()
    window, n_lines = window if isinstance(window, tuple) else (window, None)
    window = np.frombuffer(window.encode(), np.uint8)
    n = window.shape[0]
    if n_lines is None:
        n_lines = int((window == 10).sum()) + int(window[-1] != 10)
    n_pad = pad_bucket(n, min_bucket=16384)
    l_pad = pad_bucket(n_lines + 1)
    lane_bytes = np.full(n_pad + jp._TAIL_PAD, 0x20, np.uint8)
    lane_bytes[:n] = window
    with jax.enable_x64(True):
        got = jp._parse_fn_cached(n_pad, l_pad, False)(
            lane_bytes, np.int32(n_lines))
        want = _segment_reduce_reference(n_pad, l_pad)(
            lane_bytes, np.int32(n_lines))
    for name, g, w in zip(("vals", "spans", "flags", "window_ok"), got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert np.array_equal(g, w), (name, np.argwhere(g != w)[:5])


@pytest.mark.parametrize("n", [1, 1000, 1024, 4096, 5000, (1 << 21) + 2048])
@pytest.mark.parametrize("scan", ["cumsum", "cummax", "cummin",
                                  "cummin_reverse"])
def test_chunked_scan_matches_numpy_accumulate(scan, n):
    from delta_tpu.ops import scans

    x = np.random.default_rng(n).integers(-1 << 30, 1 << 30, n,
                                          dtype=np.int32)
    got, want = {
        "cumsum": lambda: (scans.cumsum_1d(x), np.cumsum(x, dtype=np.int32)),
        "cummax": lambda: (scans.cummax_1d(x), np.maximum.accumulate(x)),
        "cummin": lambda: (scans.cummin_1d(x), np.minimum.accumulate(x)),
        "cummin_reverse": lambda: (
            scans.cummin_1d(x, reverse=True),
            np.minimum.accumulate(x[::-1])[::-1]),
    }[scan]()
    assert np.array_equal(np.asarray(got), want)


# ------------------------------------------------- device DV decode ---------

def _mask_parity(vals, n):
    from delta_tpu.dv.roaring import RoaringBitmapArray, decode_delta_mask

    bm = RoaringBitmapArray(np.asarray(vals, np.uint64))
    out = decode_delta_mask(bm.serialize_delta(), n)
    assert out is not None
    mask, card = out
    assert np.array_equal(mask, bm.to_mask(n))
    assert card == bm.cardinality
    return mask


def test_dv_decode_array_bitmap_parity(monkeypatch):
    monkeypatch.setenv("DELTA_TPU_DEVICE_DV_DECODE", "1")
    rng = np.random.default_rng(3)
    # array containers (sparse)
    _mask_parity(rng.choice(100000, 500, replace=False), 100000)
    # bitmap container (dense)
    _mask_parity(rng.choice(70000, 20000, replace=False), 70000)
    # mixed containers across several 16-bit keys
    vals = np.concatenate([
        rng.choice(65536, 64, replace=False).astype(np.uint64),
        rng.choice(65536, 8000, replace=False).astype(np.uint64) + (1 << 16),
        rng.choice(65536, 10, replace=False).astype(np.uint64) + (5 << 16),
    ])
    _mask_parity(vals, 1 << 20)
    # rows beyond n: mask truncates, cardinality still counts them
    _mask_parity([1, 5, 99, 150, 200], 100)
    # empty
    _mask_parity([], 64)


def test_dv_decode_run_container_parity(monkeypatch):
    """Hand-built run-container blob (our serializer never emits runs,
    Spark's does)."""
    monkeypatch.setenv("DELTA_TPU_DEVICE_DV_DECODE", "1")
    from delta_tpu.dv.roaring import (DELTA_MAGIC, RoaringBitmapArray,
                                      decode_delta_mask)

    runs = [(10, 5), (100, 3), (40000, 100)]
    body = bytearray()
    body += struct.pack("<HH", 12347, 0)  # run cookie, (n-1)=0 containers
    body += bytes([1])  # run-flag bitset: container 0 is a run container
    card = sum(l for _, l in runs)
    body += struct.pack("<HH", 0, card - 1)
    body += struct.pack("<H", len(runs))  # no offsets (< 4 containers)
    for start, length in runs:
        body += struct.pack("<HH", start, length - 1)
    blob = (struct.pack("<i", DELTA_MAGIC) + struct.pack("<q", 1)
            + struct.pack("<I", 0) + bytes(body))

    bm = RoaringBitmapArray.deserialize_delta(blob)
    out = decode_delta_mask(blob, 65536)
    assert out is not None
    mask, dcard = out
    assert np.array_equal(mask, bm.to_mask(65536))
    assert dcard == bm.cardinality == card


def test_dv_decode_gate_off_and_high_bucket(monkeypatch):
    from delta_tpu.dv.roaring import RoaringBitmapArray, decode_delta_mask

    blob = RoaringBitmapArray(np.array([1, 2, 3], np.uint64)).serialize_delta()
    monkeypatch.delenv("DELTA_TPU_DEVICE_DV_DECODE", raising=False)
    assert decode_delta_mask(blob, 10) is None  # gate off
    monkeypatch.setenv("DELTA_TPU_DEVICE_DV_DECODE", "1")
    # >2^32 address space exceeds _MAX_DECODE_WORDS -> host fallback
    hi = RoaringBitmapArray(np.array([3, 1 << 33], np.uint64))
    assert decode_delta_mask(hi.serialize_delta(), 100) is None


def test_load_deletion_vector_mask_routes(tmp_path, monkeypatch):
    """Descriptor-level mask API: identical masks whichever route runs,
    and the declared-cardinality check fires on both."""
    from delta_tpu.dv.descriptor import (inline_descriptor,
                                         load_deletion_vector_mask)
    from delta_tpu.dv.roaring import RoaringBitmapArray
    from delta_tpu.errors import DeletionVectorError

    bm = RoaringBitmapArray(np.array([0, 3, 9, 40000], np.uint64))
    row = inline_descriptor(bm).to_dict()

    monkeypatch.delenv("DELTA_TPU_DEVICE_DV_DECODE", raising=False)
    host = load_deletion_vector_mask(None, "/t", row, 50000)
    monkeypatch.setenv("DELTA_TPU_DEVICE_DV_DECODE", "1")
    dev = load_deletion_vector_mask(None, "/t", row, 50000)
    assert np.array_equal(host, dev)
    assert host.sum() == 4 and host[3] and host[40000]

    bad = dict(row, cardinality=17)
    for env in ("0", "1"):
        monkeypatch.setenv("DELTA_TPU_DEVICE_DV_DECODE", env)
        with pytest.raises(DeletionVectorError):
            load_deletion_vector_mask(None, "/t", bad, 50000)


# ------------------------------------------------- bit-width guards ---------

def test_hybrid_width_guard_surfaces_decode_error():
    from delta_tpu.log.page_decode import DecodeUnsupported, parse_hybrid

    with pytest.raises(DecodeUnsupported):
        parse_hybrid(b"\x00" * 8, 0, 33, 4)
    with pytest.raises(DecodeUnsupported):
        parse_hybrid(b"\x00" * 8, 0, -2, 4)
