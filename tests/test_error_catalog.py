"""Error-class catalog: every concrete error type resolves to a stable
class with an SQLSTATE (the reference's delta-error-classes.json role)."""

import inspect

import delta_tpu.errors as E
from delta_tpu.errors import DeltaError, error_catalog, error_info


def _concrete_error_classes():
    out = []
    for _, obj in inspect.getmembers(E, inspect.isclass):
        if issubclass(obj, DeltaError):
            out.append(obj)
    # classes defined elsewhere that carry their own error_class
    from delta_tpu.commands.merge import MergeCardinalityError
    from delta_tpu.log.segment import CorruptLogError

    out += [MergeCardinalityError, CorruptLogError]
    return out


def test_every_error_class_is_in_the_catalog():
    catalog = error_catalog()
    for cls in _concrete_error_classes():
        assert cls.error_class in catalog, cls.__name__
        entry = catalog[cls.error_class]
        assert entry["sqlState"]
        assert entry["message"]


def test_error_classes_are_unique_where_distinct():
    seen = {}
    for cls in _concrete_error_classes():
        if cls.error_class in seen and seen[cls.error_class] is not cls:
            # subclass sharing a parent's class is allowed only for
            # aliases; distinct top-level types must not collide
            assert issubclass(cls, seen[cls.error_class]) or issubclass(
                seen[cls.error_class], cls), (
                f"{cls.__name__} and {seen[cls.error_class].__name__} share "
                f"{cls.error_class}")
        seen.setdefault(cls.error_class, cls)


def test_error_info_structure():
    try:
        raise E.VersionNotFoundError(version=7, earliest=0, latest=3)
    except DeltaError as e:
        info = error_info(e)
    assert info["errorClass"] == "DELTA_VERSION_NOT_FOUND"
    assert info["sqlState"] == "42815"
    assert info["parameters"]["version"] == 7
    assert "version" in info["messageTemplate"]


# ---- package walk: every raise site is typed + cataloged (r4) --------

import ast
import os

PKG = os.path.dirname(E.__file__)

# exceptions that are NOT user-facing Delta errors: builtins for
# internal invariants, storage-protocol exceptions with documented
# contracts, and parse-layer locals
_ALLOWED_NON_DELTA = {
    "ValueError", "TypeError", "KeyError", "RuntimeError", "IOError",
    "OSError", "FileNotFoundError", "FileExistsError",
    "NotImplementedError", "StopIteration", "TimeoutError",
    "AssertionError", "ConnectionError", "InterruptedError",
    "FileAlreadyExistsError", "PreconditionFailedError",
    "TableAlreadyExistsError", "TableNotInCatalogError",
    "ParseError", "CommitFailedException",
    # internal fall-back signal of the page decoder: always caught,
    # the Arrow reader takes over (log/page_decode.py)
    "DecodeUnsupported",
    # its twin on the write side: the checkpoint encode's stitcher
    # meeting a footer it does not carry; always caught, the one
    # `pq.write_table` call takes over (log/parquet_stitch.py)
    "StandDown",
    # storage-protocol error carrying the DynamoDB __type; the arbiter
    # maps the arbitration-relevant case (ConditionalCheckFailed) to
    # FileAlreadyExistsError like the other store clients
    "DynamoDbError",
    # storage-protocol IOError subclasses: StorageRequestError carries
    # the HTTP status the resilience classifier keys on; ChaosError is
    # the chaos harness's injected (always-transient) fault
    "StorageRequestError", "ChaosError",
    # device-chaos twins: seeded injections at the dispatch funnel,
    # classified by retryable/markers like real runtime errors
    # (resilience/device_chaos.py)
    "DeviceChaosError", "DeviceResourceExhaustedError",
}


def _raise_sites():
    for root, _dirs, files in os.walk(PKG):
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(root, f)
            tree = ast.parse(open(path).read(), filename=path)
            for node in ast.walk(tree):
                if not isinstance(node, ast.Raise) or node.exc is None:
                    continue
                exc = node.exc
                if isinstance(exc, ast.Call):
                    exc = exc.func
                if isinstance(exc, ast.Name):
                    yield path, node.lineno, exc.id
                elif isinstance(exc, ast.Attribute):
                    yield path, node.lineno, exc.attr


def test_no_generic_delta_error_raises():
    """All 204 former `raise DeltaError(...)` sites were mapped to
    typed classes in round 4; this pins the count at zero."""
    generic = [f"{os.path.relpath(p, PKG)}:{ln}"
               for p, ln, name in _raise_sites() if name == "DeltaError"]
    assert not generic, (
        f"raise a typed, cataloged subclass instead: {generic}")


def test_every_raise_site_is_typed_or_allowed():
    known = {n for n, obj in inspect.getmembers(E, inspect.isclass)
             if issubclass(obj, DeltaError)}
    # typed DeltaError subclasses defined next to their subsystem
    known |= {"MergeCardinalityError", "CorruptLogError",
              "RemoteDeltaError", "PostCommitHookError",
              "SchemaEvolutionRequiresRestart", "CheckpointWriteError"}
    extra_builtin = {"AttributeError", "EOFError", "SystemExit"}
    bad = []
    for p, ln, name in _raise_sites():
        if name in known or name in _ALLOWED_NON_DELTA \
                or name in extra_builtin:
            continue
        if name.startswith("_"):
            continue  # module-internal control-flow exceptions
        if name[0].islower() or name in ("e", "err", "exc"):
            continue  # re-raise of a caught local
        bad.append(f"{os.path.relpath(p, PKG)}:{ln}: {name}")
    assert not bad, f"unclassified raise sites: {bad}"


def test_catalog_round5_floor():
    # reference catalog is ~448 classes; round 5 target was >=200
    assert len(error_catalog()) >= 200


# ---- raisability census: no dead catalog entries (r5) ----------------

def _class_defaults():
    """class name -> default error_class, from every ClassDef in the
    package (AST, so subsystem-local classes count too)."""
    out = {}
    for root, _dirs, files in os.walk(PKG):
        for f in files:
            if not f.endswith(".py"):
                continue
            tree = ast.parse(open(os.path.join(root, f)).read())
            for node in ast.walk(tree):
                if not isinstance(node, ast.ClassDef):
                    continue
                for st in node.body:
                    if isinstance(st, ast.Assign):
                        for tg in st.targets:
                            if isinstance(tg, ast.Name) \
                                    and tg.id == "error_class" \
                                    and isinstance(st.value, ast.Constant):
                                out[node.name] = st.value.value
    return out


def _produced_classes():
    """Error classes some raise site actually produces: an explicit
    error_class= kwarg, or the raised type's default."""
    defaults = _class_defaults()
    produced = set()
    raised_types = set()
    for root, _dirs, files in os.walk(PKG):
        for f in files:
            if not f.endswith(".py"):
                continue
            tree = ast.parse(open(os.path.join(root, f)).read())
            for node in ast.walk(tree):
                if not (isinstance(node, ast.Raise)
                        and isinstance(node.exc, ast.Call)):
                    continue
                call = node.exc
                ec = next((kw.value.value for kw in call.keywords
                           if kw.arg == "error_class"
                           and isinstance(kw.value, ast.Constant)), None)
                fn = call.func
                name = fn.id if isinstance(fn, ast.Name) else (
                    fn.attr if isinstance(fn, ast.Attribute) else None)
                if name:
                    raised_types.add(name)
                if ec is not None:
                    produced.add(ec)
                elif name in defaults:
                    produced.add(defaults[name])
    return produced, raised_types, defaults


def test_every_catalog_class_is_raisable():
    """No dead entries: every catalog class is either produced by a
    raise site, or is the family default of an exception type that IS
    raised (sites may narrow the class per condition, like the
    reference's DeltaErrors.scala factories), or the default of a base
    class whose subclasses are raised (e.g. ConcurrentModification)."""
    produced, raised_types, defaults = _produced_classes()
    family_defaults = {defaults[t] for t in raised_types
                       if t in defaults}
    # base classes of raised subclasses
    base_classes = set()
    for _n, obj in inspect.getmembers(E, inspect.isclass):
        if issubclass(obj, DeltaError) and obj.__name__ in raised_types:
            for parent in obj.__mro__[1:]:
                if parent is DeltaError or not issubclass(parent,
                                                          DeltaError):
                    break
                base_classes.add(parent.error_class)
    # classes the AST census cannot attribute to a raise site:
    # UnsupportedTableFeatureError picks its class inside __init__, and
    # MergeBuilder._validate_clauses raises through a data-driven loop
    # (error_class=ec) — covered by test_merge_clause_validation
    special = {
        "DELTA_UNSUPPORTED_FEATURES_FOR_WRITE",
        "DELTA_NON_LAST_MATCHED_CLAUSE_OMIT_CONDITION",
        "DELTA_NON_LAST_NOT_MATCHED_CLAUSE_OMIT_CONDITION",
        "DELTA_NON_LAST_NOT_MATCHED_BY_SOURCE_CLAUSE_OMIT_CONDITION",
    }
    ok = produced | family_defaults | base_classes | special | \
        {"DELTA_ERROR"}
    dead = sorted(set(error_catalog()) - ok)
    assert not dead, f"catalog entries no raise site can produce: {dead}"


def test_every_explicit_error_class_is_cataloged():
    """The inverse: every error_class= string used at a raise site (and
    every class default) exists in the catalog — no typo'd classes."""
    produced, _raised, defaults = _produced_classes()
    catalog = error_catalog()
    unknown = sorted((produced | set(defaults.values())) - set(catalog))
    assert not unknown, f"uncataloged error classes in use: {unknown}"


# ---- behavior tests for the round-5 validations ----------------------

def test_new_validation_conditions(tmp_path):
    """The genuinely-new checks added with their catalog classes."""
    import pyarrow as pa
    import pytest

    import delta_tpu.api as dta
    from delta_tpu.errors import DeltaError, error_info
    from delta_tpu.table import Table

    p = str(tmp_path / "t")
    dta.write_table(p, pa.table({"id": pa.array([1, 2], pa.int64())}))
    t = Table.for_path(p)

    def klass(fn):
        with __import__("pytest").raises(DeltaError) as ei:
            fn()
        return error_info(ei.value)["errorClass"]

    # CDC range start > end
    from delta_tpu.read.cdc import table_changes
    from delta_tpu.sql import sql

    sql(f"ALTER TABLE '{p}' SET TBLPROPERTIES "
        f"('delta.enableChangeDataFeed' = 'true')")
    assert klass(lambda: table_changes(t, 5, 1)) == "DELTA_INVALID_CDC_RANGE"

    # time travel: both version and timestamp
    assert klass(lambda: dta.read_table(p, version=0, timestamp_ms=1)) \
        == "DELTA_ONEOF_IN_TIMETRAVEL"

    # unset non-existent property
    from delta_tpu.commands.alter import unset_properties

    assert klass(lambda: unset_properties(t, ["delta.nope"])) \
        == "DELTA_UNSET_NON_EXISTENT_PROPERTY"

    # invalid characters in column names without column mapping
    assert klass(lambda: dta.write_table(
        str(tmp_path / "bad"), pa.table({"a b": [1]}))) \
        == "DELTA_INVALID_CHARACTERS_IN_COLUMN_NAME"

    # non-boolean CHECK constraint
    from delta_tpu.constraints import add_constraint

    assert klass(lambda: add_constraint(t, "c1", "id")) \
        == "DELTA_NON_BOOLEAN_CHECK_CONSTRAINT"

    # malformed interval table property
    from delta_tpu.config import _parse_interval_ms

    assert klass(lambda: _parse_interval_ms("interval five days")) \
        == "DELTA_INVALID_INTERVAL"
    assert klass(lambda: _parse_interval_ms("interval")) \
        == "DELTA_INVALID_CALENDAR_INTERVAL_EMPTY"

    # reserved CDC column names on write
    assert klass(lambda: dta.write_table(
        p, pa.table({"id": [3], "_change_type": ["x"]}), mode="append")) \
        == "RESERVED_CDC_COLUMNS_ON_WRITE"


def test_error_info_subclassed_iceberg_compat(tmp_path):
    """Dotted subclass keys (the reference's errorClass.subClass shape)
    resolve through error_info."""
    from delta_tpu.errors import error_catalog

    entry = error_catalog()[
        "DELTA_ICEBERG_COMPAT_VIOLATION.DELETION_VECTORS_SHOULD_BE_DISABLED"]
    assert entry["sqlState"]


def test_invalid_column_chars_nested_and_alter(tmp_path):
    """The name-character rule holds at every schema change (the
    update_metadata choke point), including nested struct fields and
    ALTER ADD COLUMNS — not just top-level creation."""
    import pyarrow as pa
    import pytest

    import delta_tpu.api as dta
    from delta_tpu.commands.alter import add_columns
    from delta_tpu.errors import DeltaError, error_info
    from delta_tpu.models.schema import LONG, StructField
    from delta_tpu.table import Table

    # nested struct child with a bad name
    p1 = str(tmp_path / "nested")
    nested = pa.table({"s": pa.array([{"a b": 1}],
                                     pa.struct([("a b", pa.int64())]))})
    with pytest.raises(DeltaError) as ei:
        dta.write_table(p1, nested)
    assert error_info(ei.value)["errorClass"] == \
        "DELTA_INVALID_CHARACTERS_IN_COLUMN_NAME"

    # ALTER ADD COLUMNS with a bad name on an existing table
    p2 = str(tmp_path / "plain")
    dta.write_table(p2, pa.table({"id": pa.array([1], pa.int64())}))
    with pytest.raises(DeltaError) as ei:
        add_columns(Table.for_path(p2), [StructField("a b", LONG)])
    assert error_info(ei.value)["errorClass"] == \
        "DELTA_INVALID_CHARACTERS_IN_COLUMN_NAME"


def test_round5_command_validation_conditions(tmp_path):
    """Batch of reference conditions added in round 5: OPTIMIZE FULL,
    zorder-without-stats, clustering limits, restore timestamps,
    clone/convert targets, multi-format time travel."""
    import time

    import pyarrow as pa
    import pytest

    import delta_tpu.api as dta
    from delta_tpu.errors import DeltaError, error_info
    from delta_tpu.sql import sql
    from delta_tpu.table import Table

    def klass(fn):
        with pytest.raises(DeltaError) as ei:
            fn()
        return error_info(ei.value)["errorClass"]

    p = str(tmp_path / "t")
    dta.write_table(p, pa.table({
        "id": pa.array([1, 2], pa.int64()),
        "v": pa.array([1.0, 2.0]),
        "tags": pa.array([[1], [2]], pa.list_(pa.int64()))}))
    t = Table.for_path(p)

    # OPTIMIZE FULL on a non-clustered table
    assert klass(lambda: sql(f"OPTIMIZE '{p}' FULL")) \
        == "DELTA_OPTIMIZE_FULL_NOT_SUPPORTED"

    # zorder on a column with no collected stats
    sql(f"ALTER TABLE '{p}' SET TBLPROPERTIES "
        f"('delta.dataSkippingStatsColumns' = 'id')")
    assert klass(lambda: t.optimize().execute_zorder_by("v")) \
        == "DELTA_ZORDERING_ON_COLUMN_WITHOUT_STATS"

    # clustering: >4 columns / non-skippable datatype
    from delta_tpu.clustering import set_clustering_columns

    assert klass(lambda: set_clustering_columns(
        t, ["a", "b", "c", "d", "e"])) \
        == "DELTA_CLUSTER_BY_INVALID_NUM_COLUMNS"
    assert klass(lambda: set_clustering_columns(t, ["tags"])) \
        == "DELTA_CLUSTERING_COLUMNS_DATATYPE_NOT_SUPPORTED"

    # clustered OPTIMIZE rejects predicates; FULL works end-to-end
    set_clustering_columns(t, ["id"])
    from delta_tpu.expressions import col, lit

    assert klass(lambda: t.optimize().where(
        col("id") > lit(0)).execute_compaction()) \
        == "DELTA_CLUSTERING_WITH_PARTITION_PREDICATE"
    m = t.optimize().execute_full()
    assert m.num_files_added >= 1

    # restore to out-of-range timestamps
    from delta_tpu.commands.restore import restore

    assert klass(lambda: restore(t, timestamp_ms=1)) \
        == "DELTA_CANNOT_RESTORE_TIMESTAMP_EARLIER"
    assert klass(lambda: restore(
        t, timestamp_ms=int(time.time() * 1000) + 10**9)) \
        == "DELTA_CANNOT_RESTORE_TIMESTAMP_GREATER"

    # clone into a non-empty, non-table directory
    from delta_tpu.commands.restore import clone

    junkdir = tmp_path / "junkdir"
    junkdir.mkdir()
    (junkdir / "x.bin").write_bytes(b"x")
    assert klass(lambda: clone(t, str(junkdir))) \
        == "DELTA_UNSUPPORTED_NON_EMPTY_CLONE"

    # convert: missing / non-parquet provider
    assert klass(lambda: sql(f"CONVERT TO DELTA '{p}'")) \
        == "DELTA_MISSING_PROVIDER_FOR_CONVERT"
    assert klass(lambda: sql(f"CONVERT TO DELTA iceberg.'{p}'")) \
        == "DELTA_CONVERT_NON_PARQUET_TABLE"

    # both time-travel formats on one table reference
    from delta_tpu.sqlengine import execute_select

    assert klass(lambda: execute_select(
        f"SELECT * FROM '{p}' VERSION AS OF 0 TIMESTAMP AS OF 1")) \
        == "DELTA_UNSUPPORTED_TIME_TRAVEL_MULTIPLE_FORMATS"


def test_round5_streaming_cdc_validation_conditions(tmp_path):
    """Streaming option/offset validation + CDC boundary classes."""
    import pyarrow as pa
    import pytest

    import delta_tpu.api as dta
    from delta_tpu.errors import DeltaError, error_info
    from delta_tpu.sql import sql
    from delta_tpu.streaming import DeltaSource, DeltaSourceOffset
    from delta_tpu.table import Table

    def klass(fn):
        with pytest.raises(DeltaError) as ei:
            fn()
        return error_info(ei.value)["errorClass"]

    p = str(tmp_path / "t")
    dta.write_table(p, pa.table({"id": pa.array([1, 2], pa.int64())}))
    dta.write_table(p, pa.table({"id": pa.array([3], pa.int64())}),
                    mode="append")
    t = Table.for_path(p)

    # option parsing
    assert klass(lambda: DeltaSource.from_options(
        t, {"startingVersion": "banana"})) == "DELTA_INVALID_SOURCE_VERSION"
    assert klass(lambda: DeltaSource.from_options(
        t, {"startingVersion": "1", "startingTimestamp": "1"})) \
        == "DELTA_STARTING_VERSION_AND_TIMESTAMP_BOTH_SET"
    assert klass(lambda: DeltaSource.from_options(
        t, {"maxFilesPerTrigger": "0"})) == "DELTA_UNKNOWN_READ_LIMIT"
    assert klass(lambda: DeltaSource.from_options(
        t, {"ignoreDeletes": "maybe"})) == "DELTA_ILLEGAL_OPTION"
    src, limits = DeltaSource.from_options(
        t, {"startingVersion": "latest", "maxFilesPerTrigger": "7"})
    assert limits.max_files == 7
    assert src.latest_offset() is None  # nothing after "latest"

    # startingTimestamp resolves to the first commit at/after it
    ts1 = t.snapshot_at(1)  # noqa: F841 — materialize version 1
    from delta_tpu.history import get_history

    hist = {r.version: r.timestamp_ms for r in get_history(t)}
    src2, _ = DeltaSource.from_options(
        t, {"startingTimestamp": str(hist[1])})
    off = src2.latest_offset()
    batch = src2.get_batch(None, off)
    assert sorted(batch.column("id").to_pylist()) == [3]  # v1 only

    # offset wire-format validation
    assert klass(lambda: DeltaSourceOffset.from_json("not json")) \
        == "DELTA_INVALID_SOURCE_OFFSET_FORMAT"
    assert klass(lambda: DeltaSourceOffset.from_json(
        '{"sourceVersion": 99, "reservoirVersion": 1, "index": -1}')) \
        == "DELTA_INVALID_SOURCE_VERSION"
    rt = DeltaSourceOffset.from_json(
        DeltaSourceOffset(1, -1, reservoir_id="abc").to_json())
    assert rt.reservoir_id == "abc" and rt.reservoir_version == 1

    # offset from a different table id is rejected
    src3 = DeltaSource(t)
    foreign = DeltaSourceOffset(0, -1, reservoir_id="some-other-table")
    assert klass(lambda: src3.latest_offset(foreign)) \
        == "DIFFERENT_DELTA_TABLE_READ_BY_STREAMING_SOURCE"

    # CDC boundary validation
    from delta_tpu.read.cdc import table_changes

    sql(f"ALTER TABLE '{p}' SET TBLPROPERTIES "
        f"('delta.enableChangeDataFeed' = 'true')")  # version 2
    assert klass(lambda: table_changes(t)) == "DELTA_NO_START_FOR_CDC_READ"
    assert klass(lambda: table_changes(
        t, starting_version=0, starting_timestamp=1)) \
        == "DELTA_MULTIPLE_CDC_BOUNDARY"
    assert klass(lambda: table_changes(
        t, starting_version=0, ending_version=1, ending_timestamp=2)) \
        == "DELTA_MULTIPLE_CDC_BOUNDARY"
    # the pre-enablement range never recorded change data
    assert klass(lambda: table_changes(t, starting_version=0)) \
        == "DELTA_MISSING_CHANGE_DATA"
    # post-enablement range works, including timestamp boundaries
    dta.write_table(p, pa.table({"id": pa.array([4], pa.int64())}),
                    mode="append")  # version 3
    changes = table_changes(t, starting_version=3)
    assert changes.column("id").to_pylist() == [4]
    hist = {r.version: r.timestamp_ms for r in get_history(t)}
    by_ts = table_changes(t, starting_timestamp=hist[3])
    assert by_ts.column("id").to_pylist() == [4]


def test_round5_schema_conf_dv_validation_conditions(tmp_path):
    """Batch C: property/coordinated-commits guards, nested ALTER
    errors, partition validation, DV descriptor validation."""
    import dataclasses

    import pyarrow as pa
    import pytest

    import delta_tpu.api as dta
    from delta_tpu.errors import DeltaError, error_info
    from delta_tpu.table import Table

    def klass(fn):
        with pytest.raises(DeltaError) as ei:
            fn()
        return error_info(ei.value)["errorClass"]

    p = str(tmp_path / "t")
    dta.write_table(p, pa.table({
        "id": pa.array([1, 2], pa.int64()),
        "s": pa.array([{"a": 1}, {"a": 2}],
                      pa.struct([("a", pa.int64())]))}))
    t = Table.for_path(p)

    from delta_tpu.commands.alter import (
        add_columns,
        drop_column,
        set_properties,
        unset_properties,
    )
    from delta_tpu.models.schema import LONG, StructField

    # unknown delta.* property / bad value / bad autoCompact value
    assert klass(lambda: set_properties(
        t, {"delta.checkpointIntervall": "10"})) \
        == "DELTA_UNKNOWN_CONFIGURATION"
    assert klass(lambda: set_properties(
        t, {"delta.checkpointInterval": "many"})) \
        == "DELTA_VIOLATE_TABLE_PROPERTY_VALIDATION_FAILED"
    assert klass(lambda: set_properties(
        t, {"delta.autoOptimize.autoCompact": "sometimes"})) \
        == "DELTA_INVALID_AUTO_COMPACT_TYPE"

    # coordinated-commits guards (non-CC table first)
    from delta_tpu.coordinatedcommits.client import (
        COORDINATOR_CONF_KEY,
        COORDINATOR_NAME_KEY,
        TABLE_CONF_KEY,
    )

    assert klass(lambda: set_properties(
        t, {COORDINATOR_NAME_KEY: "x"})) \
        == "DELTA_MUST_SET_ALL_COORDINATED_COMMITS_CONFS_IN_COMMAND"
    assert klass(lambda: set_properties(
        t, {COORDINATOR_NAME_KEY: "x", COORDINATOR_CONF_KEY: "{}",
            TABLE_CONF_KEY: "{}"})) \
        == "DELTA_CONF_OVERRIDE_NOT_SUPPORTED_IN_COMMAND"
    assert klass(lambda: set_properties(
        t, {COORDINATOR_NAME_KEY: "x", COORDINATOR_CONF_KEY: "{}",
            "delta.enableInCommitTimestamps": "true"})) \
        == "DELTA_CANNOT_SET_COORDINATED_COMMITS_DEPENDENCIES"
    # now a CC table (simulated existing confs)
    from delta_tpu.coordinatedcommits.client import (
        validate_cc_alter_set,
        validate_cc_alter_unset,
    )

    existing = {COORDINATOR_NAME_KEY: "c", COORDINATOR_CONF_KEY: "{}"}
    assert klass(lambda: validate_cc_alter_set(
        existing, {COORDINATOR_NAME_KEY: "other",
                   COORDINATOR_CONF_KEY: "{}"})) \
        == "DELTA_CANNOT_OVERRIDE_COORDINATED_COMMITS_CONFS"
    assert klass(lambda: validate_cc_alter_set(
        existing, {"delta.enableInCommitTimestamps": "false"})) \
        == "DELTA_CANNOT_MODIFY_COORDINATED_COMMITS_DEPENDENCIES"
    assert klass(lambda: validate_cc_alter_unset(
        existing, [COORDINATOR_NAME_KEY])) \
        == "DELTA_CANNOT_UNSET_COORDINATED_COMMITS_CONFS"
    assert klass(lambda: validate_cc_alter_unset(
        existing, ["delta.enableInCommitTimestamps"])) \
        == "DELTA_CANNOT_MODIFY_COORDINATED_COMMITS_DEPENDENCIES"
    # plain property set/unset still works
    set_properties(t, {"delta.checkpointInterval": "20",
                       "myapp.custom": "anything"})
    unset_properties(t, ["myapp.custom"])

    # nested ALTER errors + the working nested paths
    assert klass(lambda: add_columns(
        t, [StructField("nope.b", LONG)])) \
        == "DELTA_ADD_COLUMN_STRUCT_NOT_FOUND"
    assert klass(lambda: add_columns(
        t, [StructField("id.b", LONG)])) \
        == "DELTA_ADD_COLUMN_PARENT_NOT_STRUCT"
    add_columns(t, [StructField("s.b", LONG)])
    snap = t.latest_snapshot()
    s_field = next(f for f in snap.schema.fields if f.name == "s")
    assert [f.name for f in s_field.dataType.fields] == ["a", "b"]
    assert klass(lambda: drop_column(t, "id.x")) \
        == "DELTA_UNSUPPORTED_DROP_COLUMN"  # mapping off first
    set_properties(t, {"delta.columnMapping.mode": "name"})
    assert klass(lambda: drop_column(t, "id.x")) \
        == "DELTA_UNSUPPORTED_DROP_NESTED_COLUMN_FROM_NON_STRUCT_TYPE"
    drop_column(t, "s.b")
    snap = t.latest_snapshot()
    s_field = next(f for f in snap.schema.fields if f.name == "s")
    assert [f.name for f in s_field.dataType.fields] == ["a"]

    # partition validation at metadata update
    assert klass(lambda: dta.write_table(
        str(tmp_path / "allpart"),
        pa.table({"a": [1], "b": [2]}), partition_by=["a", "b"])) \
        == "DELTA_CANNOT_USE_ALL_COLUMNS_FOR_PARTITION"
    assert klass(lambda: dta.write_table(
        str(tmp_path / "badpart"),
        pa.table({"a": [1], "s": pa.array(
            [{"x": 1}], pa.struct([("x", pa.int64())]))}),
        partition_by=["s"])) == "DELTA_INVALID_PARTITION_COLUMN_TYPE"

    # DV descriptor out of sync with its bitmap
    from delta_tpu.dv.descriptor import load_deletion_vector
    from delta_tpu.dv.roaring import RoaringBitmapArray
    import base64

    import numpy as np

    bm = RoaringBitmapArray(np.array([1, 5, 9], np.uint64))
    blob = bm.serialize_delta()
    inline = base64.b85encode(blob).decode()
    good = {"storageType": "i", "pathOrInlineDv": inline,
            "sizeInBytes": len(blob), "cardinality": 3}
    assert list(load_deletion_vector(t.engine, p, good)) == [1, 5, 9]
    assert klass(lambda: load_deletion_vector(
        t.engine, p, {**good, "sizeInBytes": len(blob) + 1})) \
        == "DELTA_DELETION_VECTOR_SIZE_MISMATCH"
    assert klass(lambda: load_deletion_vector(
        t.engine, p, {**good, "cardinality": 7})) \
        == "DELTA_DELETION_VECTOR_CARDINALITY_MISMATCH"


def test_round5_review_fix_regressions(tmp_path):
    """Regressions for the round-5 review findings."""
    import time as _time

    import pyarrow as pa
    import pytest

    import delta_tpu.api as dta
    from delta_tpu.errors import DeltaError, error_info
    from delta_tpu.sql import sql
    from delta_tpu.table import Table

    def klass(fn):
        with pytest.raises(DeltaError) as ei:
            fn()
        return error_info(ei.value)["errorClass"]

    p = str(tmp_path / "t")
    dta.write_table(p, pa.table({"id": pa.array([1], pa.int64())}),
                    properties={"delta.enableChangeDataFeed": "true"})
    _time.sleep(0.05)
    dta.write_table(p, pa.table({"id": pa.array([2], pa.int64())}),
                    mode="append")
    t = Table.for_path(p)

    # CDC startingTimestamp is at-or-AFTER: a midpoint timestamp must
    # exclude the earlier commit
    from delta_tpu.history import get_history
    from delta_tpu.read.cdc import table_changes

    hist = {r.version: r.timestamp_ms for r in get_history(t)}
    assert hist[1] > hist[0], "need distinct mtimes for the boundary"
    mid = hist[0] + 1
    ch = table_changes(t, starting_timestamp=mid)
    assert ch.column("id").to_pylist() == [2]

    # a trailing token named 'version' after a time-travel clause must
    # produce a clean parse error, not an IndexError (the multi-format
    # lookahead reads one token past the clause)
    from delta_tpu.errors import SqlParseError

    with pytest.raises(SqlParseError):
        sql(f"SELECT id FROM '{p}' VERSION AS OF 0 version")

    # inventory vacuum must NOT advance the LITE watermark
    import json as _json
    import os as _os

    inv = pa.table({"path": ["x"], "length": [1], "isDir": [False],
                    "modificationTime": [0]})
    t.vacuum(retention_hours=0, inventory=inv)
    info = _os.path.join(p, "_delta_log", "_last_vacuum_info")
    assert not _os.path.exists(info)

    # corrupted sourceVersion type -> offset-format error, not ValueError
    from delta_tpu.streaming import DeltaSourceOffset

    assert klass(lambda: DeltaSourceOffset.from_json(
        '{"reservoirVersion": 1, "index": -1, "sourceVersion": "abc"}')) \
        == "DELTA_INVALID_SOURCE_OFFSET_FORMAT"

    # OPTIMIZE FULL + ZORDER BY is contradictory, not silently dropped
    assert klass(lambda: sql(
        f"OPTIMIZE '{p}' FULL ZORDER BY (id)")) \
        == "DELTA_CLUSTERING_WITH_ZORDER_BY"

    # every boolean property validates strictly at SET time
    from delta_tpu.commands.alter import set_properties

    assert klass(lambda: set_properties(
        t, {"delta.appendOnly": "yess"})) \
        == "DELTA_VIOLATE_TABLE_PROPERTY_VALIDATION_FAILED"


def test_round5_colgen_write_log_validation_conditions(tmp_path):
    """Batch D: identity/generated declaration + dependency guards,
    empty data, INSERT mismatch, log-integrity classes."""
    import os as _os

    import pyarrow as pa
    import pytest

    import delta_tpu.api as dta
    from delta_tpu.colgen import generated_field, identity_field
    from delta_tpu.errors import DeltaError, error_info
    from delta_tpu.models.schema import (
        LONG,
        STRING,
        StructField,
        StructType,
        schema_to_json,
    )
    from delta_tpu.sql import sql
    from delta_tpu.table import Table

    def klass(fn):
        with pytest.raises(DeltaError) as ei:
            fn()
        return error_info(ei.value)["errorClass"]

    def create(schema_fields, path, partition_by=None):
        t = Table.for_path(str(tmp_path / path))
        b = t.create_transaction_builder("CREATE TABLE") \
            .with_schema(schema_to_json(StructType(schema_fields)))
        if partition_by:
            b = b.with_partition_columns(partition_by)
        return b.build()

    # identity declaration invariants
    ident = identity_field("id")
    both = StructField("id", LONG, metadata={
        "delta.identity.start": 1, "delta.identity.step": 1,
        "delta.generationExpression": "x"})
    assert klass(lambda: create([both, StructField("x", LONG)], "t1")) \
        == "DELTA_IDENTITY_COLUMNS_WITH_GENERATED_EXPRESSION"
    assert klass(lambda: create([ident, StructField("x", LONG)], "t2",
                                partition_by=["id"])) \
        == "DELTA_IDENTITY_COLUMNS_PARTITION_NOT_SUPPORTED"
    bad_type = StructField("id", STRING, metadata={
        "delta.identity.start": 1, "delta.identity.step": 1})
    assert klass(lambda: create([bad_type, StructField("x", LONG)],
                                "t3")) \
        == "DELTA_IDENTITY_COLUMNS_UNSUPPORTED_DATA_TYPE"
    gen_bad = generated_field("g", LONG, "missing_col")
    assert klass(lambda: create([StructField("x", LONG), gen_bad],
                                "t4")) \
        == "DELTA_INVALID_GENERATED_COLUMN_REFERENCES"
    assert klass(lambda: create([], "t5")) == "DELTA_EMPTY_DATA"

    # UPDATE of an identity column
    p = str(tmp_path / "ident")
    t = Table.for_path(p)
    t.create_transaction_builder("CREATE TABLE").with_schema(
        schema_to_json(StructType([ident, StructField("x", LONG)]))
    ).build().commit()
    dta.write_table(p, pa.table({"x": pa.array([1, 2], pa.int64())}),
                    mode="append")
    from delta_tpu.commands.dml import update
    from delta_tpu.expressions import col, lit

    assert klass(lambda: update(t, {"id": lit(99)}, col("x") > lit(0))) \
        == "DELTA_IDENTITY_COLUMNS_UPDATE_NOT_SUPPORTED"

    # dependent-column guards (generated + constraint)
    p2 = str(tmp_path / "dep")
    t2 = Table.for_path(p2)
    t2.create_transaction_builder("CREATE TABLE").with_schema(
        schema_to_json(StructType([
            StructField("base", LONG),
            StructField("other", LONG),
            generated_field("twice", LONG, "base")]))
    ).build().commit()
    from delta_tpu.commands.alter import rename_column, set_properties

    set_properties(t2, {"delta.columnMapping.mode": "name"})
    from delta_tpu.commands.alter import drop_column
    from delta_tpu.constraints import add_constraint

    assert klass(lambda: drop_column(t2, "base")) \
        == "DELTA_GENERATED_COLUMNS_DEPENDENT_COLUMN_CHANGE"
    assert klass(lambda: rename_column(t2, "base", "b2")) \
        == "DELTA_GENERATED_COLUMNS_DEPENDENT_COLUMN_CHANGE"
    add_constraint(t2, "pos", "other > 0")
    assert klass(lambda: drop_column(t2, "other")) \
        == "DELTA_CONSTRAINT_DEPENDENT_COLUMN_CHANGE"

    # MERGE INSERT column/value count mismatch shares the arity class
    p3 = str(tmp_path / "ins")
    dta.write_table(p3, pa.table({"a": pa.array([1], pa.int64())}))
    assert klass(lambda: sql(
        f"MERGE INTO '{p3}' AS t USING '{p3}' AS s ON t.a = s.a "
        "WHEN NOT MATCHED THEN INSERT (a) VALUES (s.a, 1)")) \
        == "DELTA_INSERT_COLUMN_ARITY_MISMATCH"

    # mid-range log hole past the checkpoint -> not contiguous
    p4 = str(tmp_path / "gap")
    for i in range(4):
        dta.write_table(p4, pa.table({"a": pa.array([i], pa.int64())}),
                        mode="error" if i == 0 else "append")
    t4 = Table.for_path(p4)
    from delta_tpu.streaming import DeltaSource

    _os.unlink(_os.path.join(p4, "_delta_log", f"{2:020d}.json"))
    # a FRESH listing detects the hole at segment build
    assert klass(lambda: Table.for_path(p4).latest_snapshot()) \
        == "DELTA_TRUNCATED_TRANSACTION_LOG"
    # the streaming guard sees the hole only through a CACHED listing
    # (the segment still brackets the vanished commit); it must
    # classify it as non-contiguous, not as expiry
    from delta_tpu.streaming.source import _ExpiryGuard

    class _StubSeg:
        version = 3
        checkpoint_version = None
        deltas = [type("F", (), {"path": _os.path.join(
            p4, "_delta_log", f"{v:020d}.json")})() for v in (1, 2, 3)]

    class _StubSnap:
        log_segment = _StubSeg()

    class _StubTable:
        engine = t4.engine
        log_path = t4.log_path

        def latest_snapshot(self):
            return _StubSnap()

    guard = _ExpiryGuard(_StubTable(), "stream")
    assert klass(lambda: guard.check(2)) \
        == "DELTA_VERSIONS_NOT_CONTIGUOUS"


def test_round5_dependency_guard_review_fixes(tmp_path):
    """Nested-path dependency guards + generated-referencing-generated
    rejection (review findings)."""
    import pyarrow as pa
    import pytest

    from delta_tpu.errors import DeltaError, error_info
    from delta_tpu.models.schema import (
        LONG,
        StructField,
        StructType,
        schema_to_json,
    )
    from delta_tpu.table import Table
    from delta_tpu.colgen import generated_field

    def klass(fn):
        with pytest.raises(DeltaError) as ei:
            fn()
        return error_info(ei.value)["errorClass"]

    # generated column referencing another generated column
    t0 = Table.for_path(str(tmp_path / "gg"))
    b = t0.create_transaction_builder("CREATE TABLE").with_schema(
        schema_to_json(StructType([
            StructField("x", LONG),
            generated_field("g1", LONG, "x"),
            generated_field("g2", LONG, "g1")])))
    assert klass(lambda: b.build().commit()) \
        == "DELTA_INVALID_GENERATED_COLUMN_REFERENCES"

    # generated column referencing a NESTED field blocks dropping it
    p = str(tmp_path / "nested")
    t = Table.for_path(p)
    inner = StructType([StructField("x", LONG), StructField("y", LONG)])
    t.create_transaction_builder("CREATE TABLE").with_schema(
        schema_to_json(StructType([
            StructField("s", inner),
            generated_field("g", LONG, "s.x")]))).build().commit()
    from delta_tpu.commands.alter import drop_column, set_properties

    set_properties(t, {"delta.columnMapping.mode": "name"})
    assert klass(lambda: drop_column(t, "s.x")) \
        == "DELTA_GENERATED_COLUMNS_DEPENDENT_COLUMN_CHANGE"
    assert klass(lambda: drop_column(t, "s")) \
        == "DELTA_GENERATED_COLUMNS_DEPENDENT_COLUMN_CHANGE"
    drop_column(t, "s.y")  # un-referenced sibling drops fine


def test_round5_dynamic_overwrite_and_schema_log(tmp_path):
    """Batch E: dynamic partition overwrite (feature + guards),
    dataChange=false discipline, schema-log integrity classes."""
    import pyarrow as pa
    import pytest

    import delta_tpu.api as dta
    from delta_tpu.errors import DeltaError, error_info
    from delta_tpu.expressions import col, lit
    from delta_tpu.table import Table

    def klass(fn):
        with pytest.raises(DeltaError) as ei:
            fn()
        return error_info(ei.value)["errorClass"]

    p = str(tmp_path / "t")
    dta.write_table(p, pa.table({
        "id": pa.array([1, 2, 3, 4], pa.int64()),
        "part": pa.array(["a", "a", "b", "b"])}),
        partition_by=["part"])

    # dynamic overwrite replaces ONLY the partitions in the new data
    dta.write_table(p, pa.table({
        "id": pa.array([10], pa.int64()),
        "part": pa.array(["a"])}),
        mode="overwrite", partition_overwrite_mode="dynamic")
    out = dta.read_table(p).sort_by("id")
    assert out.column("id").to_pylist() == [3, 4, 10]
    assert sorted(set(out.column("part").to_pylist())) == ["a", "b"]

    # option conflicts
    assert klass(lambda: dta.write_table(
        p, pa.table({"id": pa.array([1], pa.int64()),
                     "part": pa.array(["a"])}),
        mode="overwrite", partition_overwrite_mode="dynamic",
        replace_where=col("part") == lit("a"))) \
        == "DELTA_REPLACE_WHERE_WITH_DYNAMIC_PARTITION_OVERWRITE"
    assert klass(lambda: dta.write_table(
        p, pa.table({"id": pa.array([1], pa.int64()),
                     "part": pa.array(["a"])}),
        mode="overwrite", partition_overwrite_mode="dynamic",
        overwrite_schema=True)) \
        == "DELTA_OVERWRITE_SCHEMA_WITH_DYNAMIC_PARTITION_OVERWRITE"
    assert klass(lambda: dta.write_table(
        p, pa.table({"id": pa.array([1], pa.int64()),
                     "part": pa.array(["a"])}),
        mode="overwrite", data_change=False,
        replace_where=col("part") == lit("a"))) \
        == "DELTA_REPLACE_WHERE_WITH_FILTER_DATA_CHANGE_UNSET"
    assert klass(lambda: dta.write_table(
        str(tmp_path / "new"), pa.table({"id": pa.array([1], pa.int64())}),
        data_change=False)) == "DELTA_DATA_CHANGE_FALSE"
    assert klass(lambda: dta.write_table(
        p, pa.table({"id": pa.array([1], pa.int64()),
                     "part": pa.array(["a"])}),
        mode="overwrite", partition_overwrite_mode="sideways")) \
        == "DELTA_ILLEGAL_OPTION"

    # dataChange=false writes rearrangement adds streams must skip
    v = dta.write_table(p, pa.table({
        "id": pa.array([99], pa.int64()),
        "part": pa.array(["c"])}), mode="append", data_change=False)
    from delta_tpu.models.actions import (
        AddFile,
        actions_from_commit_bytes,
    )
    from delta_tpu.utils import filenames

    t = Table.for_path(p)
    acts = actions_from_commit_bytes(t.engine.fs.read_file(
        filenames.delta_file(t.log_path, v)))
    adds = [a for a in acts if isinstance(a, AddFile)]
    assert adds and all(not a.dataChange for a in adds)

    # schema-log integrity
    from delta_tpu.streaming.schema_log import (
        PersistedMetadata,
        SchemaTrackingLog,
    )

    loc = str(tmp_path / "ckpt")
    log = SchemaTrackingLog(t.engine, loc, "table-A")
    log.append(PersistedMetadata(0, "{}", ["part"], {}))
    # partition schema change is rejected
    assert klass(lambda: log.append(
        PersistedMetadata(1, "{}", ["other"], {}))) \
        == "DELTA_STREAMING_SCHEMA_LOG_INCOMPATIBLE_PARTITION_SCHEMA"
    # wrong table id in a persisted entry
    log2 = SchemaTrackingLog(t.engine, loc, "table-A")
    import os as _os

    evil = _os.path.join(loc, "_schema_log_table-A",
                         f"{1:020d}.json")
    with open(evil, "w") as f:
        f.write(PersistedMetadata(1, "{}", ["part"], {},
                                  table_id="table-B").to_json())
    assert klass(lambda: log2.entries()) \
        == "DELTA_STREAMING_SCHEMA_LOG_INCOMPATIBLE_DELTA_TABLE_ID"
    # corrupt entry
    with open(evil, "w") as f:
        f.write("{not json")
    assert klass(lambda: log2.entries()) \
        == "DELTA_STREAMING_SCHEMA_LOG_DESERIALIZE_FAILED"


def test_round5_batch_e_review_fixes(tmp_path):
    """Review regressions: consistent dataChange on overwrite removes,
    MERGE identity guard, unparseable generation expressions."""
    import pyarrow as pa
    import pytest

    import delta_tpu.api as dta
    from delta_tpu.errors import DeltaError, error_info
    from delta_tpu.models.schema import (
        LONG,
        StructField,
        StructType,
        schema_to_json,
    )
    from delta_tpu.table import Table

    def klass(fn):
        with pytest.raises(DeltaError) as ei:
            fn()
        return error_info(ei.value)["errorClass"]

    # rearrangement overwrite: BOTH adds and removes carry
    # dataChange=false
    p = str(tmp_path / "re")
    dta.write_table(p, pa.table({"id": pa.array([1, 2], pa.int64())}))
    v = dta.write_table(p, pa.table({"id": pa.array([1, 2], pa.int64())}),
                        mode="overwrite", data_change=False)
    from delta_tpu.models.actions import (
        AddFile,
        RemoveFile,
        actions_from_commit_bytes,
    )
    from delta_tpu.utils import filenames

    t = Table.for_path(p)
    acts = actions_from_commit_bytes(
        t.engine.fs.read_file(filenames.delta_file(t.log_path, v)))
    assert all(not a.dataChange for a in acts
               if isinstance(a, (AddFile, RemoveFile)))

    # MERGE update of an identity column is rejected at analysis
    from delta_tpu.colgen import identity_field
    from delta_tpu.expressions import col, lit

    p2 = str(tmp_path / "ident")
    t2 = Table.for_path(p2)
    t2.create_transaction_builder("CREATE TABLE").with_schema(
        schema_to_json(StructType([identity_field("id"),
                                   StructField("x", LONG)]))
    ).build().commit()
    dta.write_table(p2, pa.table({"x": pa.array([1], pa.int64())}),
                    mode="append")
    from delta_tpu.commands.merge import merge

    src = pa.table({"x": pa.array([1], pa.int64())})
    assert klass(lambda: merge(t2, src, on=col("target.x") == col("source.x"))
                 .when_matched_update(set={"id": lit(0)}).execute()) \
        == "DELTA_IDENTITY_COLUMNS_UPDATE_NOT_SUPPORTED"

    # unparseable generation expression fails at declaration
    bad = StructField("g", LONG, metadata={
        "delta.generationExpression": "1 +"})
    t3 = Table.for_path(str(tmp_path / "badgen"))
    b = t3.create_transaction_builder("CREATE TABLE").with_schema(
        schema_to_json(StructType([StructField("x", LONG), bad])))
    assert klass(lambda: b.build().commit()) \
        == "DELTA_UNSUPPORTED_EXPRESSION_GENERATED_COLUMN"
