"""Pallas TPU kernels of the LM stack: rotary position embedding and the
mamba-1 selective scan.  The DONN path has no kernel of its own: its hop
and elementwise stages are jnp (``repro.core.propagation``).

Each kernel has a pure-jnp oracle (ref.py, or the model's own scan) and a
jit'd wrapper in ops.py; off-TPU the kernels run in interpret mode so they
are validated everywhere.
"""
from repro.kernels import ops

__all__ = ["ops"]
