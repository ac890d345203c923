"""Device-route failure absorption: shed-and-retry + classify-and-fall-back.

Every gated device route (`replay`/`parse`/`decode`/`skip`/`sql`) has a
host twin, so a failed device dispatch is never fatal, but the fallback
is *disciplined*, and :func:`guarded` is the one place that knows how: a
route's executing site hands it the gate, the device thunk and the
route's cataloged fallback counter, reads the :class:`Outcome`, and keeps
only what is its own (its soft declines, its host twin under
``obs.gate_observation``)::

    out = device_faults.guarded("replay", run_device, _FALLBACKS)
    if out.fell_back is None:
        return out.value
    with obs.gate_observation("replay", "host"):
        return run_host()

:func:`shed_retry` implements HBM-pressure shed-and-retry: on an
allocation failure (``RESOURCE_EXHAUSTED``) it asks the resident ledger
(`obs/hbm.py`) to evict the cheapest-to-rebuild artifacts and retries
the dispatch exactly once; a second failure, or nothing sheddable,
propagates to the classification in :func:`try_device`.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional, TypeVar

from delta_tpu import obs

T = TypeVar("T")

_SHED_RETRIES = obs.counter("hbm.shed_retries")

# Allocation-failure shapes: real XLA allocator errors carry
# RESOURCE_EXHAUSTED in their message (jaxlib raises XlaRuntimeError,
# whose *type* varies across jaxlib versions — match text, not type);
# the injected twin (device_chaos.DeviceResourceExhaustedError) uses
# the same marker on purpose.
_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory")


def is_resource_exhausted(exc: BaseException) -> bool:
    """True when the exception looks like a device allocation failure."""
    msg = str(exc)
    return any(m in msg for m in _OOM_MARKERS)


def shed_retry(gate: str, fn: Callable[[], T]) -> T:
    """Run one device-route thunk with HBM-pressure shed-and-retry.

    On an allocation failure, ask the resident ledger to shed the
    cheapest-to-rebuild artifacts and retry ``fn`` once; any other
    exception, and a retry that fails again, propagates. The retry is
    observable: it bumps ``hbm.shed_retries`` and the ledger's shed
    counters. What was shed may be what ``fn`` reads, so ``fn`` fetches
    its resident inputs itself (the skip route's lanes re-upload)."""
    try:
        return fn()
    except Exception as exc:
        if not is_resource_exhausted(exc):
            raise
        from delta_tpu.obs import hbm
        n, _freed = hbm.shed()
        if not n:
            raise
    # out of the handler: the failed attempt's traceback, and the device
    # arrays its frames held, are let go before the second attempt
    _SHED_RETRIES.inc()
    obs.add_event("device.shed_retry", gate=gate, evicted=n)
    return fn()


class Outcome(NamedTuple):
    """What one device attempt came to. ``fell_back`` is None unless a
    transient failure was absorbed: then it is the ``gate_fell_back``
    reason (``device-error:<Type>``) and ``value`` is None. Otherwise
    ``value`` is the thunk's return: the answer, or None where the
    route declined by itself (the site names that decline)."""
    value: Optional[object]
    fell_back: Optional[str] = None


def try_device(gate: str, device_fn: Callable[[], T]) -> Outcome:
    """Shed-and-retry, then classify (which feeds the route breaker): a
    permanent error leaves unchanged (real corruption or a genuine bug
    must surface, not be recomputed on the host), a transient one is
    the outcome's ``fell_back``. Counts nothing and reports no success:
    the early replay launch, whose second half reports through
    :func:`guarded`, calls this directly."""
    try:
        return Outcome(shed_retry(gate, device_fn))
    except Exception as e:
        from delta_tpu.parallel.gate import route_failed
        from delta_tpu.resilience.classify import TRANSIENT
        if route_failed(gate, e) != TRANSIENT:
            raise
        return Outcome(None, f"device-error:{type(e).__name__}")


def guarded(gate: str, device_fn: Callable[[], T], fallbacks) -> Outcome:
    """Run one gated route's device thunk under the route contract.

    A transient failure bumps ``fallbacks`` (the route's cataloged
    counter), then marks the gate record fallen back with the outcome's
    reason; the caller runs its host twin. A non-None return reports
    success to the route breaker (closing a half-open probe); None is
    the route's soft decline and reports nothing."""
    out = try_device(gate, device_fn)
    if out.fell_back is not None:
        fallbacks.inc()
        obs.gate_fell_back(gate, "host", reason=out.fell_back)
    elif out.value is not None:
        from delta_tpu.parallel.gate import route_ok
        route_ok(gate)
    return out
