"""TpuEngine: the TPU-backed Engine implementation.

I/O and byte decode (JSON/Parquet/filesystem) stay host-side — a
deliberate, measured boundary (docs/architecture.md "Device-compute
boundary"): raw-byte wrangling on device would ship MORE over the
host<->device link than the 1-2 bits/row the host encoder produces. The
device owns the regular columnar work:

- snapshot state reconstruction: jit'd sort + segmented last-wins reduce
  (`delta_tpu.ops.replay`), blockwise past HBM, sharded over a
  `jax.sharding.Mesh` or on the host twin: which of them runs is
  `parallel/gate.py::replay_kernel`'s answer, from this engine's `mesh`
  and the row count;
- MERGE match-finding: sort/segment equi-join (`delta_tpu.ops.join`);
- data-skipping predicate evaluation over the stats index
  (`delta_tpu.stats.skipping`);
- stats aggregation (min/max/nullCount) for written files and checkpoint
  summaries;
- Z-order / Hilbert curve keys for OPTIMIZE.

This class is the rebuild's counterpart of registering a new `Engine` with
the kernel (`kernel-defaults` `DefaultEngine.java:24` being the sibling).
"""

from __future__ import annotations

import os
from typing import Optional

from delta_tpu.engine.host import HostEngine
from delta_tpu.storage.logstore import logstore_for_path

_CACHE_CONFIGURED = False

# fixed path inside the checkout: the directory is part of the cache key,
# so a cache under $HOME or a temp name never hits in a relocated run
_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compilation_cache() -> None:
    """Point JAX at a persistent compilation cache so a fresh process
    pays ~0.2s for a snapshot load instead of a multi-second XLA compile
    of the replay kernel's shape bucket. `JAX_COMPILATION_CACHE_DIR`
    places it (JAX reads that variable itself, so nothing is set here);
    otherwise it lives at `<checkout>/.jax_cache`."""
    global _CACHE_CONFIGURED
    if _CACHE_CONFIGURED:
        return
    _CACHE_CONFIGURED = True
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    if jax.config.jax_compilation_cache_dir:
        return  # caller already configured a cache through jax.config
    os.makedirs(_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", _CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


class TpuEngine(HostEngine):
    use_device_replay = True
    # SQL engine relational spine (join/group-by/window sort) runs on
    # the device kernels in ops/sqlops.py; see sqlengine/device.py
    use_device_sql = True
    # checkpoint Parquet page decode through the one-lane batched plan
    # (log/page_decode.py + ops/page_decode.py): same autodetect
    # contract as parse/skip — Arrow stays the CPU default, the routing
    # itself lives in parallel/gate.py::decode_route.
    use_device_decode = False
    # checkpoint-write stats aggregation on device (ops/stats.py):
    # autodetected from the backend at construction — on a real
    # accelerator the snapshot's columnar state is already resident and
    # the aggregation is one batched dispatch; on CPU backends the host
    # numpy twin is bit-identical and skips the dispatch overhead.
    # DELTA_TPU_DEVICE_CKPT_STATS=1|0 overrides at the call site.
    use_device_ckpt_stats = False
    # batched data-skipping over the resident stats index
    # (ops/skipping.py): same autodetect contract — the numpy twin is
    # bit-identical and dispatch-free on CPU backends.
    use_device_skip = False

    def __init__(
        self,
        store_resolver=logstore_for_path,
        metrics_reporters=None,
        mesh=None,
        replay_shards: Optional[int] = None,
    ):
        super().__init__(store_resolver, metrics_reporters)
        configure_compilation_cache()
        from delta_tpu.expressions.device_eval import DeviceExpressionHandler

        self.expressions = DeviceExpressionHandler()
        # An explicitly supplied mesh (or shard count) carries intent:
        # the profitability gate must not demote it to single-chip on
        # small tables (tests shard 1k-row logs on purpose).
        self._mesh_forced = mesh is not None or (replay_shards or 0) > 1
        if mesh is None:
            mesh = _default_mesh(replay_shards)
        self.mesh = mesh
        self.replay_shards = replay_shards
        from delta_tpu.ops.stats import accel_backend_default

        self.use_device_ckpt_stats = accel_backend_default()
        # device JSON action parse (ops/json_parse.py): same
        # autodetect contract — profitable only when a real accelerator
        # runs the structural scan; the host C++ scanner stays the CPU
        # default. DELTA_TPU_DEVICE_PARSE=force|off overrides
        # (parallel/gate.py::parse_route).
        self.use_device_parse = accel_backend_default()
        # scan-plan data skipping through the resident stats index:
        # the lanes live in HBM across scans of one version, so on an
        # accelerator the whole conjunct list is one dispatch.
        # DELTA_TPU_DEVICE_SKIP=force|off overrides
        # (parallel/gate.py::skip_route).
        self.use_device_skip = accel_backend_default()
        # checkpoint page decode (one dispatch per part): profitable
        # when the raw page bytes beat the Arrow decode rate over the
        # measured link. DELTA_TPU_DEVICE_DECODE=force|off overrides
        # (parallel/gate.py::decode_route).
        self.use_device_decode = accel_backend_default()


def _default_mesh(replay_shards: Optional[int]):
    """Sharded replay is the product default whenever >1 device is
    visible. DELTA_TPU_REPLAY_SHARDS overrides the shard count; "0" or
    "1" disables sharding entirely."""
    env = os.environ.get("DELTA_TPU_REPLAY_SHARDS")
    if env is not None:
        replay_shards = int(env)
    if replay_shards is not None and replay_shards <= 1:
        return None
    import jax

    n = len(jax.devices())
    if replay_shards is not None:
        n = min(n, replay_shards)
    if n <= 1:
        return None
    from delta_tpu.parallel.mesh import make_mesh

    return make_mesh(n_devices=n)


def default_engine(**kwargs) -> TpuEngine:
    """The engine used when callers don't pass one."""
    return TpuEngine(**kwargs)
