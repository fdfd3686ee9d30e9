"""95th percentile over all machines of the window: the start of the
``build()`` that held the machine to its artifact's last file on disk."""
import numpy as np


def read(ctx):
    if not ctx["ready_s"]:
        return None
    return float(np.percentile(ctx["ready_s"], 95))
