"""Persistent XLA compilation cache setup — the one place that decides where
compiled programs are kept, called by every entry point that compiles
(``build``, ``batch-build``, ``run-server``, ``drift-rebuilder``, the bench
section children, ``chip_smoke.py``).

Placement: ``$JAX_COMPILATION_CACHE_DIR`` when set (that directory and no
other), otherwise :data:`DEFAULT_CACHE_DIR`, one fixed git-ignored path
inside the checkout. A cache that moves never hits, so the path carries
nothing that varies between runs or hosts.

Effectiveness is observable: :func:`setup_persistent_xla_cache` records the
cache's entry count and byte size at startup into the telemetry registry
(observability/metrics.py), and :func:`record_cache_growth` re-measures at
export time — entries gained during the process are cold compiles that
future builds will skip."""

import logging
import os
import re
import threading
from typing import Optional, Tuple

# entry count at setup, so record_cache_growth can report the delta
_entries_at_setup: Optional[int] = None
_cache_dir: Optional[str] = None

# ---------------------------------------- cosmetic AOT-warning filter
# XLA tuning pseudo-features: the CPU AOT loader includes them in its
# feature fingerprint, so two processes on the SAME host can disagree on
# exactly these and nothing else — the loader then warns ("could lead to
# execution errors such as SIGILL") about a mismatch that cannot SIGILL.
# The round-4 bench drowned in these. A mismatch on any *real* ISA
# feature (avx512f, sve, ...) still warns loudly.
_COSMETIC_FEATURES = frozenset({"prefer-no-gather", "prefer-no-scatter"})

_QUOTED_RE = re.compile(r"['\"]([^'\"]*)['\"]")


def _feature_sets(message: str):
    """CPU-feature token sets parsed from the warning's quoted feature
    lists (tokens split on ',', leading +/- stripped)."""
    sets = []
    for quoted in _QUOTED_RE.findall(message):
        if "+" not in quoted and "," not in quoted:
            continue
        tokens = {
            part.strip().lstrip("+-")
            for part in quoted.replace("+", ",").split(",")
            if part.strip().lstrip("+-")
        }
        if tokens:
            sets.append(tokens)
    return sets


def host_cpu_features() -> frozenset:
    """The host's CPU feature tokens (x86 ``flags`` / arm64 ``Features``
    from /proc/cpuinfo) — the feature set XLA:CPU AOT code generation keys
    on, and therefore the set a shipped-program manifest records so a
    loading host can classify a fingerprint mismatch as cosmetic or real
    (serializer/programs.py). Empty when /proc/cpuinfo is unreadable."""
    try:
        with open("/proc/cpuinfo") as fh:
            for line in fh:
                if line.startswith(("flags", "Features")):
                    _, _, value = line.partition(":")
                    return frozenset(value.split())
    except OSError:
        pass
    return frozenset()


def is_cosmetic_feature_diff(a, b) -> bool:
    """True when two CPU-feature sets differ ONLY by the cosmetic XLA
    tuning pseudo-features (``prefer-no-gather``/``prefer-no-scatter``) —
    the set-level twin of :func:`is_cosmetic_aot_mismatch`, used by the
    shipped-program loader to accept an artifact whose host fingerprint
    differs for reasons that cannot SIGILL. An identical pair is cosmetic
    too (the fingerprint then differed on something outside the feature
    set, e.g. the processor model string). Any real ISA difference
    (avx512f, sve, ...) is NOT cosmetic."""
    return (set(a) ^ set(b)) <= _COSMETIC_FEATURES


def is_cosmetic_aot_mismatch(message: str) -> bool:
    """True only when the message is the AOT feature-mismatch warning AND
    every differing feature is a cosmetic tuning pseudo-feature. Parsing
    failure means False — unknown mismatches stay loud."""
    if "SIGILL" not in message and "execution errors" not in message:
        return False
    sets = _feature_sets(message)
    if len(sets) < 2:
        return False
    diff = sets[0] ^ sets[1]
    return bool(diff) and diff <= _COSMETIC_FEATURES


class CosmeticAotMismatchFilter(logging.Filter):
    """Drops the known-cosmetic ``+prefer-no-gather``/``+prefer-no-scatter``
    AOT loader warning at the logging layer; any genuine feature mismatch
    passes through untouched (pinned by tests/gordo_tpu/test_xla_cache.py).
    """

    def filter(self, record: logging.LogRecord) -> bool:
        try:
            message = record.getMessage()
        except Exception:  # noqa: BLE001 — never break logging itself
            return True
        return not is_cosmetic_aot_mismatch(message)


_AOT_FILTER = CosmeticAotMismatchFilter()


def install_aot_warning_filter() -> None:
    """Attach the cosmetic-mismatch filter to every jax logger that exists
    (whichever module the XLA:CPU AOT loader warning surfaces through) and
    to the warnings-module capture. Filters don't propagate to parent
    loggers, so each gets its own; idempotent, because
    logging.Logger.addFilter is a no-op for an already-attached filter."""
    import jax  # noqa: F401 — importing creates the per-module jax loggers

    names = ["jax", "py.warnings"] + [
        name for name in logging.root.manager.loggerDict
        if name.startswith("jax.")
    ]
    for name in names:
        logging.getLogger(name).addFilter(_AOT_FILTER)
    for handler in logging.getLogger().handlers:
        handler.addFilter(_AOT_FILTER)


def cache_stats(cache_dir: str) -> Tuple[int, int]:
    """(entry_count, total_bytes) of a persistent-cache directory; (0, 0)
    when it does not exist yet (jax creates it on first persisted compile)."""
    entries = 0
    total_bytes = 0
    try:
        with os.scandir(cache_dir) as it:
            for entry in it:
                if not entry.is_file(follow_symlinks=False):
                    continue
                entries += 1
                try:
                    total_bytes += entry.stat(follow_symlinks=False).st_size
                except OSError:
                    pass
    except OSError:
        return 0, 0
    return entries, total_bytes


def record_cache_growth() -> Tuple[int, int]:
    """Refresh the cache gauges and credit entries added since the last
    measurement to the added-entries counter (the high-water mark advances,
    so repeated calls never double-count). Returns (entries, bytes)."""
    global _entries_at_setup
    from gordo_tpu.observability import metrics as metric_catalog

    if _cache_dir is None:
        return 0, 0
    entries, size = cache_stats(_cache_dir)
    metric_catalog.XLA_CACHE_ENTRIES.set(entries)
    metric_catalog.XLA_CACHE_BYTES.set(size)
    if _entries_at_setup is not None and entries > _entries_at_setup:
        metric_catalog.XLA_CACHE_ENTRIES_ADDED.inc(entries - _entries_at_setup)
        _entries_at_setup = entries
    return entries, size


def host_fingerprint() -> str:
    """Short stable hash of everything that makes an XLA:CPU AOT artifact
    host-specific: machine arch, CPU feature flags, and the jaxlib version.

    Stamped into the manifest of programs shipped with an artifact
    (serializer/programs.py): XLA:CPU AOT executables bake in the compile
    host's CPU features, and loading one on a host with different features
    warns ("could lead to execution errors such as SIGILL") and can crash.
    The persistent compile cache is NOT keyed on it — jax's own cache key
    covers what the compiler depends on.
    """
    import hashlib
    import platform

    parts = [platform.machine(), platform.processor() or ""]
    try:
        with open("/proc/cpuinfo") as fh:
            for line in fh:
                # x86 "flags", arm64 "Features" — the first such line is the
                # full feature set AOT code generation keys on
                if line.startswith(("flags", "Features")):
                    parts.append(line.strip())
                    break
    except OSError:
        pass
    try:
        import jaxlib

        parts.append(getattr(jaxlib, "__version__", ""))
    except Exception:  # noqa: BLE001
        pass
    return hashlib.sha1("|".join(parts).encode()).hexdigest()[:12]


# <checkout>/.jax_cache — fixed, inside the checkout, listed in .gitignore
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


_listening = False


def _count_compiles() -> None:
    """Feed jax's own compile events into the telemetry registry (once per
    process), so every process that compiles can say how many programs it
    compiled, how many it loaded from the persistent cache, and how long it
    waited for both — read by ``chip_smoke.py`` from the metrics file of a
    build and from ``/debug/vars`` of a server."""
    global _listening
    if _listening:
        return
    _listening = True
    import jax.monitoring

    from gordo_tpu.observability import metrics as metric_catalog

    # a cache hit is announced inside the compile-or-load call whose
    # duration event follows on the same thread
    hit = threading.local()

    def on_event(event: str, **_kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            hit.pending = True

    def on_duration(event: str, seconds: float, **_kwargs) -> None:
        if event != "/jax/core/compile/backend_compile_duration":
            return
        source = "persistent_cache" if getattr(hit, "pending", False) else "compiled"
        hit.pending = False
        metric_catalog.XLA_COMPILES.labels(source=source).inc()
        metric_catalog.XLA_COMPILE_SECONDS.inc(seconds)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


def cache_dir() -> str:
    """Where the persistent cache lives. Touches neither jax nor the disk,
    so a parent that must stay off the device can ask too."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR


def setup_persistent_xla_cache() -> str:
    """Turn on jax's persistent compile cache and return its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins and no other directory is set in
    code; without it the cache lives at :data:`DEFAULT_CACHE_DIR`. Takes the
    device (it asks jax for the backend), so call it from the process that
    computes. Raises when the directory cannot be created or jax refuses
    the setting: a process that silently compiles everything again is a
    performance bug nobody sees.
    """
    global _entries_at_setup, _cache_dir
    import jax

    # every persistent-cache user is a potential AOT-artifact loader, so
    # the cosmetic feature-mismatch warning is silenced here (genuine ISA
    # mismatches still pass the filter and stay loud)
    install_aot_warning_filter()
    path = cache_dir()
    if jax.config.jax_compilation_cache_dir != path:
        # either the default, or a variable exported after jax read its
        # environment at import
        jax.config.update("jax_compilation_cache_dir", path)
    os.makedirs(path, exist_ok=True)
    if jax.default_backend() != "cpu":
        # keep every accelerator compile whatever it cost, so that a second
        # run of the same programs compiles nothing and adds no entries. On
        # CPU jax's own threshold stays: XLA:CPU prints two multi-kilobyte
        # feature-list lines from C++ for every program it loads back, which
        # no Python log filter can drop
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # startup snapshot of cache effectiveness (warm entries available to
    # this process); export-time record_cache_growth() reports what was
    # added. Gauges are cheap and the scan is one directory listing.
    from gordo_tpu.observability import metrics as metric_catalog

    _count_compiles()
    _cache_dir = path
    entries, size = cache_stats(path)
    _entries_at_setup = entries
    metric_catalog.XLA_CACHE_ENTRIES.set(entries)
    metric_catalog.XLA_CACHE_BYTES.set(size)
    return path
