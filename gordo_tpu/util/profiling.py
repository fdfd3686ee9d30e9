"""
Opt-in JAX profiler / XLA-dump hookup.

The reference's tracing story is wall-clock only (Server-Timing headers,
build durations in metadata — SURVEY.md §5); on TPU the equivalents that
actually matter are device traces and compiled-program dumps:

- ``GORDO_TPU_PROFILE_DIR=/path``: wraps the batched fleet build (and any
  code under :func:`maybe_profile`) in a ``jax.profiler`` session — open the
  result with TensorBoard or Perfetto to see per-op device timelines,
  HBM traffic, and host/device overlap. The session holds the program's own
  stages too: span timing is on for its duration, and every live
  ``telemetry.span`` is the host mark ``gordo.<name>`` on the same clock
  (docs/observability.md names the spans and the device-side scopes).
- ``XLA_FLAGS=--xla_dump_to=/path``: XLA's own HLO dump (handled by XLA
  itself; listed here because it is the other half of the toolkit).
"""

import contextlib
import logging
import os

logger = logging.getLogger(__name__)

PROFILE_DIR_ENV = "GORDO_TPU_PROFILE_DIR"

_nested_logged = False


@contextlib.contextmanager
def session(target: str):
    """One ``jax.profiler`` session into ``target`` round the enclosed block,
    span timing on for as long as it is open. JAX allows one session a
    process: where one is open already (a benchmark harness's, or
    ``/debug/profile?device=1``) this opens none — the block runs inside
    that one, whose owner turned the spans on — and says so once. Yields
    whether this call opened the session."""
    global _nested_logged
    import jax

    from gordo_tpu.observability import telemetry

    try:
        jax.profiler.start_trace(target)
    except RuntimeError as exc:
        if "already been started" not in str(exc):
            raise
        if not _nested_logged:
            _nested_logged = True
            logger.warning(
                "a jax profiler session is already open in this process; "
                "not opening another into %s", target,
            )
        yield False
        return
    try:
        with telemetry.spans_on():
            yield True
    finally:
        jax.profiler.stop_trace()


@contextlib.contextmanager
def maybe_profile(label: str):
    """Trace the enclosed block when $GORDO_TPU_PROFILE_DIR is set."""
    profile_dir = os.environ.get(PROFILE_DIR_ENV)
    if not profile_dir:
        yield
        return
    target = os.path.join(profile_dir, label)
    os.makedirs(target, exist_ok=True)
    with session(target) as opened:
        if opened:
            logger.info("jax profiler tracing %s -> %s", label, target)
        yield
    if opened:
        logger.info("profile written: %s (open with TensorBoard/Perfetto)", target)
