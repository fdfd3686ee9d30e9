"""
Pallas TPU kernels for the framework's hot ops.

Every kernel has a numerically-matching jnp/XLA reference implementation
that tests compare it against. The kernels compile for TPU only; the CPU
tests pass ``interpret=True`` explicitly to exercise the kernel code paths.
"""

from .flash_attention import flash_attention

__all__ = ["flash_attention"]
