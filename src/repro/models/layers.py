"""Shared LM building blocks: norms, MLPs, embeddings, RoPE."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.config import LMConfig
from repro.nn import ParamSpec


# ------------------------------------------------------------------- norms
def norm_spec(cfg: LMConfig, d: Optional[int] = None):
    d = d or cfg.d_model
    return {"scale": ParamSpec((d,), jnp.float32, ("embed",), init="ones")}


def apply_norm(p, x, cfg: LMConfig):
    xf = x.astype(jnp.float32)
    if cfg.norm == "ln":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
    else:  # rmsnorm
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + cfg.norm_eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


# -------------------------------------------------------------------- mlps
def mlp_spec(cfg: LMConfig, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    if cfg.mlp in ("swiglu", "geglu"):
        return {
            "w_gate": ParamSpec((d, f), jnp.float32, ("embed", "mlp")),
            "w_up": ParamSpec((d, f), jnp.float32, ("embed", "mlp")),
            "w_down": ParamSpec((f, d), jnp.float32, ("mlp", "embed")),
        }
    return {  # plain gelu MLP
        "w_up": ParamSpec((d, f), jnp.float32, ("embed", "mlp")),
        "b_up": ParamSpec((f,), jnp.float32, ("mlp",), init="zeros"),
        "w_down": ParamSpec((f, d), jnp.float32, ("mlp", "embed")),
        "b_down": ParamSpec((d,), jnp.float32, ("embed",), init="zeros"),
    }


def apply_mlp(p, x, cfg: LMConfig):
    dt = cfg.dtype
    if cfg.mlp in ("swiglu", "geglu"):
        g = x @ p["w_gate"].astype(dt)
        u = x @ p["w_up"].astype(dt)
        act = jax.nn.silu(g) if cfg.mlp == "swiglu" else jax.nn.gelu(g)
        return (act * u) @ p["w_down"].astype(dt)
    h = x @ p["w_up"].astype(dt) + p["b_up"].astype(dt)
    h = jax.nn.gelu(h)
    return h @ p["w_down"].astype(dt) + p["b_down"].astype(dt)


# -------------------------------------------------------------- embeddings
def embed_spec(cfg: LMConfig):
    # Sharding choices here are collective-critical (EXPERIMENTS.md §Perf):
    # - table shards on EMBED only, so the token-id gather never all-gathers
    #   the table over the vocab axis;
    # - unembed stays resident vocab-sharded (TP), so the per-chunk xent
    #   matmul is local + a small logsumexp all-reduce, instead of FSDP
    #   re-gathering the unembed inside every loss chunk.
    spec = {
        "table": ParamSpec(
            (cfg.vocab, cfg.d_model), jnp.float32, (None, "embed"),
            init="embed", scale=0.02,
        )
    }
    if not cfg.tie_embeddings:
        spec["unembed"] = ParamSpec(
            (cfg.d_model, cfg.vocab), jnp.float32, (None, "vocab"),
            init="fan_in",
        )
    return spec


def embed_tokens(p, tokens, cfg: LMConfig):
    return jnp.take(p["table"], tokens, axis=0).astype(cfg.dtype)


def unembed(p, x, cfg: LMConfig):
    if cfg.tie_embeddings:
        w = p["table"].astype(cfg.dtype).T
    else:
        w = p["unembed"].astype(cfg.dtype)
    logits = x @ w
    if cfg.logit_softcap > 0.0:
        c = cfg.logit_softcap
        logits = c * jnp.tanh(logits.astype(jnp.float32) / c)
    return logits


# -------------------------------------------------------------------- rope
def rope_angles(cfg: LMConfig, positions: jax.Array):
    """cos/sin tables for positions (...,) -> (..., rot_dim//2)."""
    rot = int(cfg.head_dim * cfg.partial_rotary)
    rot -= rot % 2
    inv_freq = 1.0 / (
        cfg.rope_theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    )
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(ang), jnp.sin(ang)


def apply_rotary(x, cos, sin, cfg: LMConfig):
    """x: (B, S, H, Dh); cos/sin: (B?, S, rot//2). Rotate-half convention.

    Partial rotary (glm4): only the first ``rot`` features rotate.
    """
    rot = 2 * cos.shape[-1]
    xr, xp = x[..., :rot], x[..., rot:]
    half = rot // 2
    x1, x2 = xr[..., :half], xr[..., half:]
    c = cos[..., None, :].astype(x.dtype)  # (B?, S, 1, half)
    s = sin[..., None, :].astype(x.dtype)
    if c.ndim == 3:  # (S, 1, half) -> broadcast over batch
        c, s = c[None], s[None]
    xr = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    if xp.shape[-1] == 0:
        return xr
    return jnp.concatenate([xr, xp], axis=-1)
