"""
Pallas TPU kernels for the framework's hot ops.

Every kernel has a numerically-matching jnp/XLA reference implementation
that tests compare it against. The kernels compile for TPU only; the CPU
tests pass ``interpret=True`` explicitly to exercise the kernel code paths.

- ``flash_attention``: blockwise online-softmax attention, forward and
  backward (reference: ``ops/attention.py``'s XLA path).
- ``hc_write``: the residual streams' write ``H_res X + H_post^T y`` and its
  backward, one pass each over token tiles (reference: the jnp body of
  ``ops/nn.py _hc_write``).
"""

from .flash_attention import flash_attention
from .hc_write import hc_write

__all__ = ["flash_attention", "hc_write"]
