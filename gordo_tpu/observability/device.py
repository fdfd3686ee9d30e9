"""
Device telemetry sampler: memory, duty cycle, param-bank residency, MFU.

"Exploring the limits of Concurrency in ML Training on Google TPUs"
(PAPERS.md) frames the accounting gap this fills: serving had request
counters but no *device-utilization* story — is the accelerator actually
busy, and at what fraction of its peak? This module samples, on demand
(no background thread — it runs as a shard-flush sampler and at
``/metrics`` / ``/debug/vars`` time):

- **JAX device memory** (``memory_stats()``, absent on CPU backends —
  guarded) into ``gordo_server_device_memory_bytes{device,stat}``;
- **param-bank residency** from the cross-model batcher's device-resident
  banks: stacked bytes on device and slot occupancy (used/capacity);
- **program-cache size**: compiled stacked-apply programs held by the
  batcher's lru_cache;
- **dispatcher duty cycle** (``gordo_server_device_busy_ratio``): the
  batcher accumulates busy-seconds around every fused device call
  (``_busy_since`` window); this sampler differentiates that counter over
  the sampling interval, including the currently in-flight call;
- **online MFU** (``gordo_server_device_mfu``): the batcher also
  accumulates achieved forward FLOPs per fused call
  (:func:`~gordo_tpu.ops.flops.forward_flops_per_sample` × windows ×
  lanes); differentiated against the chip peak that
  :func:`log_placement` resolved at boot. A CPU has no peak on record, so
  there the gauge stays unset ("not measured").

:func:`describe` names the device the process runs on (platform,
``device_kind``, device count); :func:`snapshot` carries it, so a reader
of ``/debug/vars`` learns where the numbers came from, from the process
that held the chip. The chip's peak FLOP/s is looked up once, at boot
(:func:`log_placement`), where an accelerator with no peak on record fails
the boot loudly; the samplers only read what boot stored.

Everything is peek-only (never creates a batcher) and best-effort: a
sampling failure must never fail a scrape or a request.
"""

import threading
import time
from typing import Any, Dict, Optional

# memory_stats keys worth exporting (bounded label set; the full dict has
# allocator internals that vary by backend)
_MEMORY_STATS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")

_lock = threading.Lock()
# previous (monotonic, busy_seconds, flops) sample for rate derivation
_last_sample: Optional[Dict[str, float]] = None
# bf16 peak FLOP/s of this process's chip, stored by log_placement() at boot;
# None on a CPU and in a process that never booted through it
_peak_flops: Optional[float] = None


def _sample_memory() -> None:
    import jax

    from gordo_tpu.observability import metrics as metric_catalog

    for index, device in enumerate(jax.local_devices()):
        stats = getattr(device, "memory_stats", lambda: None)()
        if not isinstance(stats, dict):
            continue
        for stat in _MEMORY_STATS:
            value = stats.get(stat)
            if value is not None:
                metric_catalog.DEVICE_MEMORY.labels(
                    device=str(index), stat=stat
                ).set(float(value))


def _sample_batcher() -> float:
    """Param-bank and program-cache gauges; returns the seconds of the
    currently in-flight device call (0.0 between calls) for the duty-cycle
    sampler."""
    from gordo_tpu.observability import metrics as metric_catalog
    from gordo_tpu.server import batcher as batcher_mod

    metric_catalog.PROGRAM_CACHE_ENTRIES.set(
        batcher_mod._stacked_apply.cache_info().currsize
        + batcher_mod._single_apply.cache_info().currsize
    )
    batcher = batcher_mod.peek_batcher()
    if batcher is None:
        return 0.0
    total_bytes = 0.0
    used = 0
    capacity = 0
    for bank in list(batcher._banks.values()):
        used += len(bank)
        capacity += bank.capacity
        stacked = bank.stacked
        if stacked is not None:
            import jax

            for leaf in jax.tree_util.tree_leaves(stacked):
                total_bytes += float(getattr(leaf, "nbytes", 0))
    metric_catalog.PARAM_BANK_BYTES.set(total_bytes)
    metric_catalog.PARAM_BANK_OCCUPANCY.set(
        (used / capacity) if capacity else 0.0
    )
    return batcher.device_call_stuck_s()


def _sample_rates(inflight_s: float) -> None:
    """Differentiate the busy-seconds and achieved-FLOPs counters over the
    interval since the previous sample into the duty-cycle and online-MFU
    gauges."""
    global _last_sample
    from gordo_tpu.observability import metrics as metric_catalog

    now = time.monotonic()
    busy = metric_catalog.DEVICE_BUSY_SECONDS.value() + inflight_s
    flops = metric_catalog.DEVICE_FLOPS.value()
    with _lock:
        last = _last_sample
        _last_sample = {"t": now, "busy": busy, "flops": flops}
    if last is None:
        return
    dt = now - last["t"]
    if dt <= 0.01:
        return  # scrape storm: keep the previous ratio rather than divide
    ratio = max(0.0, busy - last["busy"]) / dt
    metric_catalog.DEVICE_BUSY_RATIO.set(min(ratio, 1.0))
    # the serving batcher dispatches to one device
    if _peak_flops:
        metric_catalog.DEVICE_MFU.set(
            max(0.0, flops - last["flops"]) / dt / _peak_flops
        )


def sample() -> None:
    """Refresh every device-telemetry gauge (best-effort per section)."""
    inflight = 0.0
    try:
        inflight = _sample_batcher()
    except Exception:  # noqa: BLE001 — sampling must not fail the caller
        pass
    try:
        _sample_memory()
    except Exception:  # noqa: BLE001
        pass
    try:
        _sample_rates(inflight)
    except Exception:  # noqa: BLE001
        pass


def describe() -> Dict[str, Any]:
    """Where this process runs, as JAX reports it: ``platform``,
    ``device_kind`` and device count. Initialises the backend if nothing
    has yet, so call it only from a process that is meant to hold the
    device."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


def log_placement(logger, command: str) -> Dict[str, Any]:
    """The one INFO line ``batch-build`` and ``run-server`` workers print at
    start, so logs say which device the numbers that follow came from. Also
    looks up the chip's peak FLOP/s for the samplers, here and only here: an
    accelerator with no peak on record (ops/flops.py raises) stops the boot,
    instead of failing every later scrape or being swallowed by one."""
    global _peak_flops
    from gordo_tpu.ops import flops as flops_mod

    placement = describe()
    _peak_flops = flops_mod.chip_peak_flops(placement["device_kind"])
    logger.info(
        "%s running on platform=%s device_kind=%r device_count=%d",
        command, placement["platform"], placement["device_kind"],
        placement["device_count"],
    )
    return placement


def snapshot() -> Dict[str, Any]:
    """Device-telemetry dict for /debug/vars (gauges refreshed first)."""
    sample()
    from gordo_tpu.observability import metrics as metric_catalog

    return {
        **describe(),
        "busy_ratio": metric_catalog.DEVICE_BUSY_RATIO.value(),
        "busy_seconds_total": metric_catalog.DEVICE_BUSY_SECONDS.value(),
        "achieved_flops_total": metric_catalog.DEVICE_FLOPS.value(),
        "online_mfu": metric_catalog.DEVICE_MFU.value(),
        "peak_flops": _peak_flops,
        "param_bank_bytes": metric_catalog.PARAM_BANK_BYTES.value(),
        "param_bank_occupancy": metric_catalog.PARAM_BANK_OCCUPANCY.value(),
        "program_cache_entries": metric_catalog.PROGRAM_CACHE_ENTRIES.value(),
    }


def install_shard_hooks() -> None:
    """Register the sampler with the shared-telemetry shard machinery so
    every flush ships fresh device gauges."""
    from gordo_tpu.observability import shared

    shared.register_sampler(sample)


def reset_for_tests() -> None:
    global _last_sample, _peak_flops
    with _lock:
        _last_sample = None
        _peak_flops = None
