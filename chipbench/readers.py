"""Helpers the per-layer metric readers share. A reader is a module
``chipbench/metrics/<metric>.py`` with ``read(ctx)``, for an end-to-end
metric as for a per-layer one. ``ctx`` holds what the harness saw of the
window: the machines its builds persisted (``machines``), its length and the
set-up before it (``window_s``, ``setup_s``), each machine's time to its
artifact (``ready_s``), the program's counters before and after it
(``before``, ``after``: the harness's own keys and every ``gordo_build_*``
series of the program's catalog under ``<name>{label=value,…}``), the cell
(``cell``), the device (``device_kind``, ``n_devices``) and, in a traced run,
the reduced trace (``trace``: the window's ``busy_s``, ``module_s``, …, and the
detail cut's ``detail_s``, ``scope_s``, ``op_s``, ``op_scope``). A reader that
finds nothing to read returns None."""

from __future__ import annotations

from typing import Optional

# the fleet trainer's chunk program: jit(vmap(one_machine)) in
# gordo_tpu/parallel/batch_trainer.py
STEP_PROGRAM = "jit_one_machine"


def counter_delta(ctx: dict, key: str) -> Optional[float]:
    """What a counter (or a phase's or histogram's sum) gained over the
    window's builds; None where the program never touched the series."""
    if key not in ctx["after"]:
        return None
    return ctx["after"][key] - ctx["before"].get(key, 0.0)


def per_machine_ms(ctx: dict, seconds: Optional[float]) -> Optional[float]:
    if seconds is None or not ctx["machines"]:
        return None
    return 1e3 * seconds / ctx["machines"]


def step_seconds(ctx: dict) -> Optional[float]:
    """Device seconds of the chunk program's executions, per device. In a CPU
    rehearsal the trace names no programs and every operation is the chunk
    program's."""
    trace = ctx["trace"]
    if not trace.module_s:
        return trace.busy_s
    total = trace.program_seconds(STEP_PROGRAM)
    return None if total is None else total / trace.n_devices
