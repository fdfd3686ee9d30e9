"""One process per chip: count a host's TPU chips and pin a process to one,
both without touching jax.

A TPU chip belongs to one process at a time. A process that initialises jax
takes every chip it can see, so a second process on the same host then fails
to start its backend. A launcher that wants several processes on one host
(``run-server`` workers) therefore has to decide, *before* any of them
initialises jax, which chip each one may see — and has to stay off jax
itself.
"""

import glob
import os

_GOOGLE_PCI_VENDOR = "0x1ae0"
# PCI device ids of TPU chips, v3 to TPU7x (the ids jax's own start-up check
# looks for)
_TPU_PCI_DEVICES = frozenset(
    {"0x0027", "0x0056", "0x005e", "0x0062", "0x0063", "0x006f", "0x0076"}
)


def _read(path: str) -> str:
    try:
        with open(path) as fh:
            return fh.read().strip()
    except OSError:
        return ""


def _chips_on_pci() -> int:
    return sum(
        1
        for vendor in glob.glob("/sys/bus/pci/devices/*/vendor")
        if _read(vendor) == _GOOGLE_PCI_VENDOR
        and _read(os.path.join(os.path.dirname(vendor), "device"))
        in _TPU_PCI_DEVICES
    )


def _chip_device_files() -> int:
    """``/dev/accel<N>`` (up to v4) or the numbered VFIO groups
    ``/dev/vfio/<N>`` (v5e and later)."""
    return len(glob.glob("/dev/accel[0-9]*")) or sum(
        os.path.basename(path).isdigit()
        for path in glob.glob("/dev/vfio/[0-9]*")
    )


def attached_tpu_chips() -> int:
    """TPU chips a process on this host can open: chips on the PCI bus that
    also have a device file. Both, because a sealed machine may list four
    chips on the bus and hand out one (seen on the chip tool's one-chip
    machine), and a VFIO group alone need not be a TPU. 0 when the process
    is held to another platform (``JAX_PLATFORMS`` set without ``tpu``),
    because then no process will take a chip."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return 0
    return min(_chips_on_pci(), _chip_device_files())


def pin_process_to_chip(index: int) -> None:
    """Make chip ``index`` the only one this process will see. Call it before
    the process initialises jax (the runtime reads these when the backend
    starts): it describes a one-chip, one-process topology, so that the
    workers of one host are independent replicas and not one slice. The
    variables are the ones jax's own multi-process TPU tests set."""
    port = str(8476 + index)
    os.environ.update(
        {
            "TPU_VISIBLE_CHIPS": str(index),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_ADDRESSES": f"localhost:{port}",
            "TPU_PROCESS_PORT": port,
            "CLOUD_TPU_TASK_ID": "0",
            # the runtime's one-process-per-host lock is per host, not per
            # chip: several pinned processes are what it has to allow
            "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
        }
    )
