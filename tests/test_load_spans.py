"""A name on every second of a cold load: the span tree of one
`latest_snapshot()` plus state read on both routes, the program names
and stage scopes of the jitted steps, the compiler's events on the
dispatch record, and the disabled path, which must stay the shared
no-op singletons with no listener and no JAX import."""

import json
import os
import re
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import delta_tpu.api as dta
from delta_tpu import Table, obs
from delta_tpu.engine.tpu import TpuEngine
from delta_tpu.obs import device as device_obs
from delta_tpu.obs import trace as trace_obs
from delta_tpu.ops import json_parse, page_decode, pallas_kernels, replay
from delta_tpu.ops import skipping, sqlops
from delta_tpu.replay.columnar import clear_parse_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMMITS = 12          # one file a commit; upstream checkpoints at 10

ROUTES = {
    "device": {"DELTA_TPU_DEVICE_PARSE": "force",
               "DELTA_TPU_REPLAY_ROUTE": "single"},
    "host": {"DELTA_TPU_DEVICE_PARSE": "off",
             "DELTA_TPU_REPLAY_ROUTE": "host"},
}

# span -> the span it sits under (None: a root of the state read)
LOAD_TREE = {
    "checkpoint.read_part": "log.read_checkpoint",
    "checkpoint.canonicalize": "log.read_checkpoint",
    "replay.keys": "snapshot.replay",
    "state.add_files_table": None,
    "state.splice_stats": "state.add_files_table",
    "state.filter_live": "state.add_files_table",
}
ROUTE_TREE = {
    "device": {**LOAD_TREE,
               "parse.wait": "parse.device_window",
               "replay.pack": "snapshot.replay",
               "replay.launch": "snapshot.replay",
               "replay.wait": "snapshot.replay",
               "replay.unpack": "snapshot.replay"},
    "host": {**LOAD_TREE, "replay.host": "snapshot.replay"},
}
CASES = [(route, name) for route, tree in ROUTE_TREE.items()
         for name in tree]


@pytest.fixture(autouse=True)
def _obs_off_afterwards():
    yield
    obs.set_trace_mode(None)
    obs.set_device_obs_mode(None)
    obs.reset_trace_buffer()
    obs.reset_device_obs()


@pytest.fixture(scope="module")
def table_path(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("load_spans") / "t")
    for i in range(COMMITS):
        rows = pa.table({"x": pa.array(np.arange(i * 100, i * 100 + 100),
                                       pa.int64())})
        dta.write_table(path, rows, mode="append" if i else "error",
                        engine=TpuEngine())
    return path


@pytest.fixture(scope="module")
def loads(table_path):
    """Per route: the spans and dispatch records of one cold load plus
    state read, and what the read returned."""
    out = {}
    for route, env in ROUTES.items():
        with pytest.MonkeyPatch.context() as mp:
            for key, value in env.items():
                mp.setenv(key, value)
            clear_parse_cache()
            obs.set_trace_mode("on")
            obs.set_device_obs_mode("on")
            obs.reset_trace_buffer()
            obs.reset_device_obs()
            snap = Table.for_path(table_path,
                                  engine=TpuEngine()).latest_snapshot()
            answer = (snap.num_files, snap.size_in_bytes,
                      snap.state.add_files_table)
            out[route] = {
                "spans": [s.to_dict() for s in obs.get_finished_spans()],
                "dispatches": obs.get_dispatch_records(),
                "gates": obs.get_gate_records(),
                "answer": answer,
                "actions": snap.state.file_actions_raw.num_rows,
                "stats_bytes": snap.state.file_actions_raw.column(
                    "stats").nbytes,
            }
            obs.set_trace_mode("off")
            obs.set_device_obs_mode("off")
    return out


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def _end(span):
    return span["start_unix_ns"] + span["duration_ns"]


@pytest.mark.parametrize("route,name", CASES)
def test_span_once_a_phase_properly_nested(loads, route, name):
    spans = loads[route]["spans"]
    by_id = {s["span_id"]: s for s in spans}
    [span] = _named(spans, name)
    parent = by_id.get(span["parent_id"])
    want = ROUTE_TREE[route][name]
    assert (parent["name"] if parent else None) == want
    if parent is not None:
        # the wall clock anchors a span, the monotonic clock times it:
        # allow the two a millisecond between them
        assert span["start_unix_ns"] >= parent["start_unix_ns"] - 1_000_000
        assert _end(span) <= _end(parent) + 1_000_000
    assert span["thread_id"] == threading.get_ident()


@pytest.mark.parametrize("route", list(ROUTES))
def test_route_holds_no_span_of_the_other_route(loads, route):
    names = {s["name"] for s in loads[route]["spans"]}
    other = set(ROUTE_TREE["host" if route == "device" else "device"])
    assert not names & (other - set(ROUTE_TREE[route]))
    # read once outside the load, once by the load's own check
    sizes = _named(loads[route]["spans"], "state.size_in_bytes")
    assert [s for s in sizes if s["parent_id"] is None] and len(sizes) <= 2


@pytest.mark.parametrize("route", list(ROUTES))
def test_span_attributes_count_the_tables_work(loads, route, table_path):
    run = loads[route]
    num_files, size, live = run["answer"]
    assert (num_files, live.num_rows) == (COMMITS, COMMITS)
    spans, n = run["spans"], run["actions"]

    def attrs(name):
        [span] = _named(spans, name)
        return span["attrs"]

    assert attrs("replay.keys")["rows"] == n
    state = attrs("state.add_files_table")
    assert (state["rows"], state["live_rows"]) == (n, num_files)
    assert state["bytes"] == live.nbytes
    assert attrs("state.filter_live")["rows"] == n
    assert attrs("state.splice_stats")["bytes"] == run["stats_bytes"] > 0
    log = os.path.join(table_path, "_delta_log")
    [part] = [f for f in os.listdir(log) if f.endswith(".parquet")]
    read = attrs("checkpoint.read_part")
    assert read["bytes"] == os.path.getsize(os.path.join(log, part))
    assert read["rows"] == attrs("checkpoint.canonicalize")["rows"]
    # a part of a few KB is no work to deal out: `pq.read_table`, whole
    assert (read["decode"], read["decode_tasks"], read["row_groups"]) == (
        "whole", 1, 1)
    # the part's adds are one run behind the protocol and metaData rows:
    # they reach the canonical table as a view, never through `filter`
    [ckpt_file] = [a for a in _named(spans, "canonicalize.filter")
                   if a["attrs"]["rows"] == read["rows"]]
    assert ckpt_file["attrs"] == {"rows": read["rows"], "kept": "view",
                                  "runs": 1, "rows_kept": COMMITS - 1}
    assert attrs("canonicalize.small_actions")["rows"] == read["rows"]
    [combine] = [a for a in _named(spans, "canonicalize.combine")
                 if a["parent_id"] == ckpt_file["parent_id"]]
    assert combine["attrs"]["rows"] == COMMITS - 1
    [columns] = [a for a in _named(spans, "canonicalize.columns")
                 if a["parent_id"] == ckpt_file["parent_id"]]
    assert columns["attrs"] == {"rows": COMMITS - 1, "escaped": 0}
    if route == "device":
        assert attrs("replay.pack")["rows"] == n
        assert attrs("replay.launch")["bytes"] == \
            attrs("replay.pack")["bytes"]
        assert attrs("replay.wait")["rows"] == n
        assert attrs("parse.wait")["bytes"] == \
            _named(spans, "parse.device_window")[0]["attrs"]["bytes"]
    else:
        assert attrs("replay.host")["rows"] == n
    assert size == sum(
        os.path.getsize(os.path.join(table_path, p))
        for p in live.column("path").to_pylist())


def _interleaved_part(tmp_path):
    """A table behind a classic checkpoint whose adds and retained
    removes lie row by row in turn, as Spark's hash-ordered state writes
    them: this library's own checkpoint of a one-file table, rewritten
    in its own schema."""
    n = 600
    path = str(tmp_path / "t")
    dta.write_table(path, pa.table({"x": pa.array([1], pa.int64())}),
                    mode="error", engine=TpuEngine())
    Table.for_path(path, engine=TpuEngine()).checkpoint()
    log = os.path.join(path, "_delta_log")
    [part] = [f for f in os.listdir(log) if f.endswith(".parquet")]
    own = pq.read_table(os.path.join(log, part))
    head = [r for r in own.to_pylist() if r["add"] is None]
    rows = [{"add": {"path": f"f{i}.parquet", "partitionValues": [],
                     "size": 1, "modificationTime": 1, "dataChange": False}}
            if i % 2 == 0 else
            {"remove": {"path": f"f{i}.parquet", "dataChange": False,
                        "deletionTimestamp": 1 << 60}}
            for i in range(n)]
    pq.write_table(pa.Table.from_pylist(head + rows, schema=own.schema),
                   os.path.join(log, part))
    os.remove(os.path.join(log, part.replace(".checkpoint.parquet", ".crc")))
    with open(os.path.join(log, "_last_checkpoint"), "w") as f:
        f.write('{"version":0,"size":%d}' % (len(head) + n))
    return path, n


def test_an_interleaved_part_goes_through_filter_and_the_counters_add_up(
        tmp_path):
    with open(os.path.join(ROOT, "delta_tpu", "resources",
                           "metric_names.json")) as f:
        catalog = json.load(f)["counters"]
    viewed, filtered = (obs.counter(f"canonicalize.rows_{how}")
                        for how in ("viewed", "filtered"))
    assert {viewed.name, filtered.name} <= set(catalog)
    path, n = _interleaved_part(tmp_path)
    clear_parse_cache()
    before = viewed.value, filtered.value
    obs.set_trace_mode("on")
    obs.reset_trace_buffer()
    snap = Table.for_path(path, engine=TpuEngine()).latest_snapshot()
    assert snap.num_files == n // 2
    spans = [s.to_dict() for s in obs.get_finished_spans()]
    found = {s["attrs"]["kept"]: s["attrs"]
             for s in _named(spans, "canonicalize.filter")}
    # the adds and the removes of the part, each a row in two
    assert [s["attrs"]["kept"] for s in _named(
        spans, "canonicalize.filter")] == ["filter", "filter"]
    assert found["filter"]["runs"] == found["filter"]["rows_kept"] == n // 2
    canonicalized = sum(s["attrs"]["rows"]
                        for s in _named(spans, "canonicalize.columns"))
    assert canonicalized == n == snap.state.file_actions_raw.num_rows
    # no `.crc` here, so `latest_snapshot` took protocol and metaData by
    # the small-action read, which goes by the part's footer: one row
    # group, read (it holds both), and of the file's bytes the footer
    # and the small columns' chunks; the full read after it is as ever
    small, full = [s["attrs"] for s in _named(spans, "checkpoint.read_part")]
    log = os.path.join(path, "_delta_log")
    [part] = [f for f in os.listdir(log) if f.endswith(".parquet")]
    size = os.path.getsize(os.path.join(log, part))
    assert full == {"bytes": size, "rows": full["rows"], "row_groups": 1,
                    "decode_tasks": 1, "decode": "whole"}
    assert small == {"bytes": size, "rows": full["rows"],
                     "file_rows": full["rows"], "row_groups": 1,
                     "row_groups_read": 1,
                     "bytes_read": small["bytes_read"]}
    assert 0 < small["bytes_read"] < size
    assert (viewed.value - before[0], filtered.value - before[1]) == (0, n)


@pytest.mark.parametrize("route", list(ROUTES))
def test_a_part_dealt_out_is_still_one_read_part_a_full_load(
        table_path, route, monkeypatch):
    """With the part's decode dealt out over the scan pool (forced: a
    byte is enough), the full load holds one `checkpoint.read_part`
    where it held it, on the driving thread, around the whole read: the
    tasks open no span, and the answer is the same."""
    from delta_tpu.engine import host

    def load():
        clear_parse_cache()
        obs.set_trace_mode("on")
        obs.reset_trace_buffer()
        snap = Table.for_path(table_path,
                              engine=TpuEngine()).latest_snapshot()
        live = snap.state.add_files_table.sort_by("path")
        spans = [s.to_dict() for s in obs.get_finished_spans()]
        obs.set_trace_mode("off")
        return (snap.num_files, live.column("path").to_pylist(),
                live.column("size").to_pylist()), spans

    for key, value in ROUTES[route].items():
        monkeypatch.setenv(key, value)
    dealt = obs.counter("checkpoint.parts_decoded_dealt")
    before = dealt.value
    whole, spans_whole = load()
    assert dealt.value == before
    monkeypatch.setattr(host, "_DEAL_MIN_BYTES", 1)
    answer, spans = load()
    assert answer == whole and answer[0] == COMMITS
    assert dealt.value - before == 1
    [read] = _named(spans, "checkpoint.read_part")
    by_id = {s["span_id"]: s for s in spans}
    assert by_id[read["parent_id"]]["name"] == "log.read_checkpoint"
    assert read["thread_id"] == threading.get_ident()
    assert read["attrs"]["decode"] == "dealt"
    assert read["attrs"]["decode_tasks"] > read["attrs"]["row_groups"] == 1
    assert read["attrs"]["rows"] == _named(
        spans_whole, "checkpoint.read_part")[0]["attrs"]["rows"]
    # the same spans, each as often, whichever way the part was decoded
    names = sorted(s["name"] for s in spans)
    assert names == sorted(s["name"] for s in spans_whole)


def test_the_wait_joins_the_dispatch_record_and_the_gate(loads):
    run = loads["device"]
    [rec] = [r for r in run["dispatches"]
             if r["kernel"].startswith("replay.single")]
    [wait] = _named(run["spans"], "replay.wait")
    assert 0 < rec["wait_ns"] <= wait["duration_ns"]
    [gate] = [g for g in run["gates"] if g["gate"] == "replay"]
    # the gate adds the two as seconds, so allow the sum's last bit
    assert gate["observed_s"] * 1e9 >= rec["wall_ns"] + rec["wait_ns"] - 1
    assert gate["observed_routes"] == ["single"]   # one launch, one route
    host = loads["host"]
    assert not [r for r in host["dispatches"]
                if r["kernel"].startswith("replay.")]


# ------------------------------------------------ names on the device ------

def _programs():
    """(program, the dispatch that launches it or the prefix it keeps)
    of every jitted step `tests/test_chip_compile.py` compiles."""
    return [
        (replay._winner_kernel, "replay.single_raw"),
        (replay._winner_kernel_fa_packed, "replay.single_fa"),
        (json_parse._parse_fn_cached(4096, 1024, False),
         "json_parse.window"),
        (page_decode._decode_fn(1024, 128, 128, 1024, 1024, 1024, True,
                                False), "page_decode.part"),
        (skipping._skip_fn_cached(2), "skipping.mask_block"),
        (sqlops._segagg_kernel, "sqlops.segagg"),
        (pallas_kernels.interleave_bits_tiled, "interleave_bits_tiled"),
        (pallas_kernels.byte_class_tiled, "byte_class_tiled"),
        (pallas_kernels.shift_extract_tiled, "shift_extract_tiled"),
    ]


@pytest.mark.parametrize("index", range(9))
def test_program_is_named_after_its_dispatch(index):
    fn, dispatch = _programs()[index]
    assert fn.__name__ == dispatch.replace(".", "_")
    assert fn.__name__ not in ("kernel", "fn", "<lambda>")


def _sources():
    for base in ("ops", "parallel", "stats"):
        folder = os.path.join(ROOT, "delta_tpu", base)
        for name in sorted(os.listdir(folder)):
            if name.endswith(".py"):
                with open(os.path.join(folder, name)) as f:
                    yield f.read()


def test_program_names_are_unique_and_join_a_dispatch():
    text = "\n".join(_sources())
    programs = re.findall(r'obs\.program\(\s*"([^"]+)"\)', text)
    dispatches = set(re.findall(r'device_dispatch\(\s*"([^"]+)"', text))
    assert len(programs) >= 28 and len(set(programs)) == len(programs)
    outside = {"sqlops.segagg", "sqlops.segagg_sharded",
               "sqlops.group_sizes", "sqlops.centered_sumsq"}
    for name in programs:     # the dispatch itself, or one of its parts
        assert (name in outside or name in dispatches
                or name.rsplit(".", 1)[0] in dispatches), name
    assert not re.search(r"jax\.jit\(\s*lambda\b", text)
    for src in _sources():    # a bare inner `kernel` / `fn` is decorated
        bare = re.findall(r"jax\.jit\(\s*(?:kernel|fn)\b", src)
        named = re.findall(
            r'@obs\.program\("[^"]+"\)\n\s+def (?:kernel|fn)\(', src)
        assert len(bare) == len(named)


PARSE_SCOPES = ("parse.classes", "parse.lines", "parse.quotes",
                "parse.depth", "parse.keys", "parse.strings", "parse.ints",
                "parse.flags")
REPLAY_SCOPES = ("replay.decode", "replay.sort", "replay.winner",
                 "replay.pack")


@pytest.fixture(scope="module")
def compiled_text():
    """The compiled HLO of the two programs a cold load runs."""
    n_pad, l_pad = 4096, 1024
    with jax.enable_x64(True):
        parse = json_parse._parse_fn_cached(n_pad, l_pad, False).lower(
            jax.ShapeDtypeStruct((n_pad + json_parse._TAIL_PAD,), jnp.uint8),
            jax.ShapeDtypeStruct((), jnp.int32)).compile().as_text()
    m, r_pad = replay.pad_bucket(5000), 1024
    layout = (m, 2, r_pad, 0)
    winner = replay._winner_kernel_fa_packed.lower(
        jax.ShapeDtypeStruct((8 + m // 8 + 2 * r_pad,), jnp.uint8),
        layout=layout).compile().as_text()
    return {"parse": parse, "winner": winner}


@pytest.mark.parametrize("program,module,scope", [
    *[("parse", "jit_json_parse_window", s) for s in PARSE_SCOPES],
    *[("winner", "jit_replay_single_fa", s) for s in REPLAY_SCOPES]])
def test_compiled_hlo_carries_the_stage_scope(compiled_text, program,
                                              module, scope):
    text = compiled_text[program]
    assert f"HloModule {module}" in text
    assert re.search(r'op_name="[^"]*/%s/' % re.escape(scope), text)


def _scatters(hlo):
    """(op_name, opcode of the combiner's root) of every scatter of a
    compiled program: a scatter that only places values has a
    combiner that returns its second parameter."""
    out = []
    for m in re.finditer(
            r' scatter\(.*to_apply=(%[\w.\-]+).*op_name="([^"]*)"', hlo):
        body = hlo[hlo.index("\n" + m.group(1) + " ("):]
        root = re.search(r"ROOT \S+ = \S+ ([\w\-]+)\(", body).group(1)
        out.append((m.group(2), root))
    return out


def test_parse_program_reduces_nothing_by_scatter(compiled_text):
    """Per-line quantities come from scans read at the line ends: the
    three scatters left place positions (line starts, line ends, quote
    ranks), and none combines with add or min as a `segment_sum` /
    `segment_min` over the byte lane does."""
    found = _scatters(compiled_text["parse"])
    assert sorted(re.search(r"parse\.\w+", name).group(0)
                  for name, _root in found) == [
        "parse.lines", "parse.lines", "parse.quotes"]
    assert {root for _name, root in found} == {"parameter"}
    probe = jax.jit(lambda x, i: jax.ops.segment_min(x, i, num_segments=8))
    reduce = probe.lower(jnp.zeros(64, jnp.int32),
                         jnp.zeros(64, jnp.int32)).compile().as_text()
    assert [root for _name, root in _scatters(reduce)] == ["minimum"]


def test_scopes_leave_the_winner_bits_alone():
    rng = np.random.default_rng(3)
    n = 5000
    keys = rng.integers(0, 900, n).astype(np.uint32)
    is_add = rng.random(n) < 0.7
    live, tomb = replay.replay_select(
        [keys], np.arange(n, dtype=np.int32), np.zeros(n, np.int32), is_add)
    want_live, want_tomb = replay.python_replay_reference(
        [(int(k),) for k in keys], np.arange(n), np.zeros(n), is_add)
    assert (live == want_live).all() and (tomb == want_tomb).all()


# ------------------------------------------- compile events ----------------

@pytest.fixture
def probe():
    """A program nothing has compiled yet, and its dispatch."""
    salt = np.random.default_rng().integers(1 << 30)
    fn = jax.jit(obs.program("test.compile_probe")(
        lambda x: x * 2 + jnp.int32(salt)))

    def launch(n=8):
        x = np.arange(n, dtype=np.int32)   # no second program to compile
        with obs.device_dispatch("test.compile_probe", key=(n,)):
            return np.asarray(fn(x))

    return launch


def test_forced_recompile_lands_on_the_open_dispatch(probe):
    obs.set_device_obs_mode("on")
    obs.reset_device_obs()
    probe()
    probe()
    first, second = obs.get_dispatch_records()
    assert first["compile_s"] > 0 and first["compile"] is True
    [program] = first["programs"]
    assert program["fun_name"] == "jit(test_compile_probe)"
    assert program["compile_s"] == first["compile_s"]
    assert program["cache_hit"] in (False, True)
    assert second["compile_s"] == 0 and "programs" not in second
    assert second["compile"] is False


def test_compile_event_is_a_span_under_the_span_open_then(probe):
    obs.set_device_obs_mode("on")
    obs.set_trace_mode("on")
    obs.reset_trace_buffer()
    with obs.span("outer") as outer:
        probe(16)
    [compile_] = _named([s.to_dict() for s in obs.get_finished_spans()],
                        "device.compile")
    assert compile_["parent_id"] == outer.span_id
    assert compile_["attrs"]["kernel"] == "test.compile_probe"
    assert compile_["attrs"]["fun_name"] == "jit(test_compile_probe)"
    [rec] = obs.get_dispatch_records()
    assert compile_["duration_ns"] == int(rec["compile_s"] * 1e9)


def test_a_compile_outside_any_dispatch_touches_no_record():
    obs.set_device_obs_mode("on")
    obs.reset_device_obs()
    salt = np.random.default_rng().integers(1 << 30)
    jax.jit(lambda x: x + jnp.int32(salt))(jnp.arange(4, dtype=jnp.int32))
    assert obs.get_dispatch_records() == []


def test_listeners_follow_the_mode():
    from jax._src import monitoring

    def held():
        return (device_obs._on_duration
                in monitoring.get_event_duration_listeners(),
                device_obs._on_time_span
                in monitoring.get_event_time_span_listeners())

    obs.set_device_obs_mode("on")
    assert held() == (True, True) and device_obs._listening
    obs.set_device_obs_mode("strict")
    assert held() == (True, True)
    obs.set_device_obs_mode("off")
    assert held() == (False, False) and not device_obs._listening


def test_wait_adds_to_the_record_and_to_the_gate_decision():
    obs.set_device_obs_mode("on")
    obs.reset_device_obs()
    obs.record_gate_decision("replay", "single", {"n_rows": 1},
                             {"single": 1.0, "host": 2.0})
    with obs.device_dispatch("replay.single_fa", key=(1,), gate="replay",
                             route="single") as dd:
        pass
    with dd.wait():
        pass
    with dd.wait():
        pass
    [rec] = obs.get_dispatch_records()
    assert rec["wait_ns"] > 0
    [gate] = obs.get_gate_records()
    assert gate["observed_s"] == pytest.approx(
        (rec["wall_ns"] + rec["wait_ns"]) / 1e9)


# ------------------------------------------- spans and the profiler --------

def test_record_span_keeps_the_events_own_clock():
    obs.set_trace_mode("on")
    obs.reset_trace_buffer()
    with obs.span("outer") as outer:
        obs.record_span("device.compile", 1_000, 250, fun_name="f")
    inner, _ = obs.get_finished_spans()
    assert (inner.start_unix_ns, inner.duration_ns) == (1_000, 250)
    assert (inner.parent_id, inner.trace_id) == (outer.span_id,
                                                 outer.trace_id)
    obs.set_trace_mode("off")
    obs.reset_trace_buffer()
    obs.record_span("device.compile", 1, 1)
    assert obs.get_finished_spans() == []


def test_live_span_enters_a_profiler_annotation(monkeypatch):
    seen = []

    class Annotation:
        def __init__(self, name, **kwargs):
            self.what = (name, kwargs)

        def __enter__(self):
            seen.append(("enter", *self.what))

        def __exit__(self, *exc):
            seen.append(("exit", *self.what))

    monkeypatch.setattr(trace_obs, "_annotation_cls", Annotation)
    obs.set_trace_mode("on")
    with obs.span("outer") as s:
        assert seen == [("enter", "outer", {"span_id": s.span_id})]
    assert seen[-1] == ("exit", "outer", {"span_id": s.span_id})
    monkeypatch.setattr(trace_obs, "_annotation_cls", None)
    with obs.span("real"):      # the real class, from the JAX loaded here
        pass
    assert trace_obs._annotation_cls is jax.profiler.TraceAnnotation


# ------------------------------------------- the disabled path -------------

def test_both_modes_off_return_the_shared_singletons():
    obs.set_trace_mode("off")
    obs.set_device_obs_mode("off")
    assert obs.span("state.add_files_table", rows=1) is obs.span("x")
    dd = obs.device_dispatch("replay.single_fa", key=(1,))
    assert dd is obs.device_dispatch("json_parse.window")
    assert dd.wait() is dd
    with obs.span("replay.wait") as sp, dd.wait():
        sp.set_attr("bytes", 1)
    assert not sp.recording and obs.get_finished_spans() == []
    assert not device_obs._listening


OFF_SCRIPT = """
import sys
from delta_tpu import obs
from delta_tpu.obs import device
{setup}
with obs.span("snapshot.load", rows=1) as sp, \\
        obs.device_dispatch("replay.single_fa", key=(1,)) as dd:
    sp.set_attr("bytes", 2)
with dd.wait():
    pass
obs.record_span("device.compile", 1, 1)
assert device._listening is False
assert "jax" not in sys.modules and "jax.profiler" not in sys.modules
print({expect})
"""


@pytest.mark.parametrize("setup,expect,want", [
    ("", "len(obs.get_finished_spans())", "0"),
    ('obs.set_trace_mode("on"); obs.set_device_obs_mode("on")',
     "[s.name for s in obs.get_finished_spans()], "
     "len(obs.get_dispatch_records())",
     "['snapshot.load', 'device.compile'] 1")])
def test_obs_imports_no_jax(setup, expect, want):
    """Off: nothing is recorded and nothing imported. On, in a process
    that never imported JAX: spans and records work, and still neither
    the profiler bridge nor the listeners pull JAX in."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("DELTA_TPU_")}
    done = subprocess.run(
        [sys.executable, "-c", OFF_SCRIPT.format(setup=setup,
                                                 expect=expect)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr
    assert done.stdout.strip() == want
