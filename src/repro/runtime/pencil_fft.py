"""Pencil-decomposed distributed 2-D FFT (beyond-paper DONN parallelism).

The paper's emulation engine is single-device (multi-GPU is future work,
§6).  For optical fields too large for one chip (e.g. 500^2+ at large
batch), we shard field ROWS over the "model" axis and implement FFT2 as:

    FFT along W (local)  ->  all-to-all row/col transpose
    -> FFT along H (local)  ->  all-to-all transpose back

which is the classic pencil/slab decomposition used by distributed FFT
libraries, expressed with jax.shard_map + lax.all_to_all.  Each FFT2 moves
2 x (field bytes) x (k-1)/k over the interconnect.

The supported entry point is :func:`local_spectral_pair` — the composed
*in-scan* form fed to ``PropagationPlan.forward/apply`` as ``spectral=``
inside an enclosing ``shard_map`` (see ``donn_steps.make_donn_sharded_
loss`` and ``InferenceEngine(model_devices=...)``).  The standalone
``pencil_fft2`` wrapper is deprecated: one shard_map per FFT call can
never fuse with the modulation between hops.

Validated against jnp.fft.fft2 in tests/test_pencil_fft.py.
"""
from __future__ import annotations

import warnings
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from jax import shard_map
from repro.runtime import sharding as shd


def _local_fft2(x, *, axis: str, k: int, inverse: bool):
    """Per-shard pencil FFT2 over the trailing (H/k, W) axes.

    Any number of leading dims (batch, channel, candidate stacks) ride
    along untouched — the all-to-all transposes address the trailing
    axes positionally, so (B, H/k, W) and (B, C, H/k, W) share one body.
    """
    fft = jnp.fft.ifft if inverse else jnp.fft.fft
    lead = x.ndim - 2  # dims left of (rows, W)
    h, W = x.shape[-2], x.shape[-1]
    x = fft(x, axis=-1)  # along W (full locally)
    x = x.reshape(x.shape[:-1] + (k, W // k))
    x = jax.lax.all_to_all(x, axis, split_axis=lead + 1,
                           concat_axis=lead, tiled=True)
    x = x[..., 0, :]  # (..., H, W/k): rows gathered, cols sharded
    x = fft(x, axis=-2)  # along H (full locally)
    H = x.shape[-2]
    x = x.reshape(x.shape[:-2] + (k, H // k, x.shape[-1]))
    x = jax.lax.all_to_all(x, axis, split_axis=lead,
                           concat_axis=lead + 2, tiled=True)
    return x[..., 0, :, :]  # (..., H/k, W)


def local_spectral_pair(axis: str, k: int):
    """(fft2, ifft2) callables for *in-scan* pencil-decomposed hops.

    Unlike ``pencil_fft2`` (which wraps its own ``shard_map``), these run
    the per-shard body directly, for use *inside* an enclosing ``shard_map``
    whose fields are row-sharded ``(..., H/k, W)`` over mesh axis ``axis``
    — e.g. as the ``spectral=`` override of ``PropagationPlan.forward`` /
    ``apply``, which puts the distributed FFT inside the fused layer scan
    (the sharded training path, ``repro.runtime.donn_steps.
    make_donn_sharded_loss``).  Both return row-sharded spectra / fields
    in the same layout, so the spectral TF multiply works on the matching
    row shard of the transfer planes with no extra communication.
    """
    return (partial(_local_fft2, axis=axis, k=k, inverse=False),
            partial(_local_fft2, axis=axis, k=k, inverse=True))


def _row_spec(axis: str):
    # (B, H, W) with H over `axis`, via the one rules table (LR109)
    return shd.rules_pspec((None, "field_h", None), {"field_h": axis})


def pencil_fft2(u, mesh: Mesh, axis: str = "model", inverse: bool = False):
    """DEPRECATED standalone FFT2 of u (B, H, W) with H sharded over ``axis``.

    One shard_map per FFT call cannot fuse with the inter-hop modulation;
    compose :func:`local_spectral_pair` into an enclosing ``shard_map``
    (the ``spectral=`` plan override) instead.  Kept one deprecation
    cycle for external callers.
    """
    warnings.warn(
        "pencil_fft2/pencil_ifft2 are deprecated: pass "
        "local_spectral_pair(axis, k) as the plan's spectral= override "
        "inside your own shard_map (see donn_steps.make_donn_sharded_loss)",
        DeprecationWarning, stacklevel=2,
    )
    k = mesh.shape[axis]
    spec = _row_spec(axis)
    fn = shard_map(
        partial(_local_fft2, axis=axis, k=k, inverse=inverse),
        mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False,
    )
    return fn(u)


def pencil_ifft2(u, mesh: Mesh, axis: str = "model"):
    return pencil_fft2(u, mesh, axis, inverse=True)


def propagate_tf_distributed(u, h_tf, mesh: Mesh, axis: str = "model"):
    """Row-sharded angular-spectrum propagation: iFFT2(FFT2(u) * H).

    The transfer function multiply is elementwise, so it runs on the
    row-sharded spectrum without any extra communication — one composed
    shard_map around the whole hop (FFT2 -> multiply -> iFFT2), not one
    per FFT.
    """
    k = mesh.shape[axis]
    fft2, ifft2 = local_spectral_pair(axis, k)

    def hop(u_loc, h_loc):
        return ifft2(fft2(u_loc) * h_loc)

    spec = _row_spec(axis)
    h_spec = shd.rules_pspec(
        ("field_h", None), {"field_h": axis}
    ) if h_tf.ndim == 2 else spec
    fn = shard_map(hop, mesh=mesh, in_specs=(spec, h_spec),
                   out_specs=spec, check_vma=False)
    return fn(u, h_tf)
