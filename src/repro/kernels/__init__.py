"""Pallas TPU kernels.

- ``fold_mix``: the folded hop's block mix in one pass, the one kernel of
  the DONN path (``repro.core.propagation`` imports it only where a folded
  scan body is traced on a TPU, so other programs never import Pallas).
- ``rope``, ``selective_scan``: the LM stack's rotary position embedding
  and mamba-1 selective scan, with jit'd wrappers in ``ops`` and a
  pure-jnp oracle in ``ref``.

Off a TPU the kernels run in interpret mode, so they are validated
everywhere.  Import a kernel's module by name: importing the package
imports none of them.
"""
