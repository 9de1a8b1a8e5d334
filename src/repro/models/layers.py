"""Shared neural-net layers (functional style; no flax on this box).

Every layer is an ``init_*(key, cfg) -> params`` / ``*_apply(params, x)``
pair over plain-dict pytrees. Layers compute in ``cfg.compute_dtype``
(bf16 by default) against fp32 master params; matmuls accumulate in fp32
via ``preferred_element_type``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def cast(x: Array, dtype) -> Array:
    return x.astype(dtype) if x.dtype != dtype else x


def dense_init(key, in_dim: int, out_shape, scale: float | None = None):
    """Normal(0, 1/sqrt(in_dim)) dense weight of shape (in_dim, *out_shape)."""
    if isinstance(out_shape, int):
        out_shape = (out_shape,)
    scale = scale if scale is not None else in_dim**-0.5
    return scale * jax.random.normal(key, (in_dim, *out_shape), jnp.float32)


def rmsnorm_init(dim: int):
    return {"scale": jnp.ones((dim,), jnp.float32)}


def rmsnorm(params, x: Array, eps: float = 1e-6) -> Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * params["scale"]
    return out.astype(dtype)


def embed_init(key, vocab: int, dim: int):
    # 1/sqrt(d) keeps untrained logits ~N(0, 1) after the final RMSNorm
    # (hidden RMS ~ 1/component), so initial CE ~ ln(V).
    return {"table": dim**-0.5 * jax.random.normal(key, (vocab, dim), jnp.float32)}


def embed(params, tokens: Array, dtype) -> Array:
    return params["table"].astype(dtype)[tokens]


def unembed(params, x: Array) -> Array:
    """Project to vocab logits; fp32 accumulation for a stable softmax-CE."""
    table = params["table"].astype(x.dtype)
    return jax.lax.dot_general(
        x, table, (((x.ndim - 1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def mlp_init(key, d_model: int, d_ff: int, activation: str = "swiglu"):
    k1, k2, k3 = jax.random.split(key, 3)
    if activation == "swiglu":
        return {
            "w_gate": dense_init(k1, d_model, d_ff),
            "w_up": dense_init(k2, d_model, d_ff),
            "w_down": dense_init(k3, d_ff, d_model),
        }
    return {
        "w_up": dense_init(k1, d_model, d_ff),
        "w_down": dense_init(k2, d_ff, d_model),
    }


def mlp_apply(params, x: Array, activation: str = "swiglu") -> Array:
    dt = x.dtype
    if activation == "swiglu":
        gate = _mm(x, params["w_gate"].astype(dt))
        up = _mm(x, params["w_up"].astype(dt))
        h = jax.nn.silu(gate) * up
    else:
        h = jax.nn.gelu(_mm(x, params["w_up"].astype(dt)))
    return _mm(h, params["w_down"].astype(dt))


def _mm(x: Array, w: Array) -> Array:
    out = jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    return out.astype(x.dtype)


def rope(x: Array, positions: Array, theta: float = 10000.0,
         seq_last: bool = False) -> Array:
    """Rotary embedding over head_dim of (..., seq, heads, head_dim), or of
    (..., heads, head_dim, seq) with ``seq_last``; positions (..., seq)."""
    axis = -2 if seq_last else -1
    half = x.shape[axis] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, half)
    if seq_last:
        angles = jnp.swapaxes(angles, -1, -2)[..., None, :, :]  # (..., 1, half, seq)
    else:
        angles = angles[..., None, :]  # broadcast over heads
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x, 2, axis=axis)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=axis)
    return out.astype(x.dtype)


def causal_conv1d_init(key, channels: int, width: int):
    return {
        "w": jax.random.normal(key, (width, channels), jnp.float32) * (width**-0.5),
        "b": jnp.zeros((channels,), jnp.float32),
    }


def causal_conv1d(params, x: Array, state: Array | None = None):
    """Depthwise causal conv over (batch, seq, channels).

    Returns (out, new_state) where state holds the trailing ``width - 1``
    inputs (the decode carry). ``state=None`` pads with zeros (train path).
    """
    w = params["w"].astype(x.dtype)  # (width, channels)
    width = w.shape[0]
    if state is None:
        pad = jnp.zeros((*x.shape[:-2], width - 1, x.shape[-1]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=-2)  # (b, seq + width - 1, c)
    out = jnp.zeros_like(x)
    for i in range(width):
        out = out + xp[..., i : i + x.shape[-2], :] * w[i]
    out = out + params["b"].astype(x.dtype)
    new_state = xp[..., -(width - 1) :, :] if width > 1 else pad
    return out, new_state


def chunked_cross_entropy(
    hidden: Array, embed_params, labels: Array, chunk: int = 512,
    unroll: bool = False,
) -> Array:
    """Mean next-token CE without materializing full (B, S, V) logits.

    Scans over sequence chunks; each chunk recomputes its logits from the
    hidden states — the (B, chunk, V) intermediate is the peak activation
    instead of (B, S, V). ``labels`` < 0 are masked out.
    """
    b, s, d = hidden.shape
    chunk = min(chunk, s)
    n_chunks = s // chunk
    rem = s - n_chunks * chunk

    @jax.checkpoint
    def chunk_loss(h, y):
        # rematerialized: without this the scan's backward stashes every
        # chunk's (b, chunk, V) logits — 37 GiB/device on the 151k-vocab
        # internvl2 train cell
        logits = unembed(embed_params, h)  # fp32 (b, chunk, V)
        mask = (y >= 0).astype(jnp.float32)
        y_safe = jnp.maximum(y, 0)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y_safe[..., None], axis=-1)[..., 0]
        return jnp.sum((logz - gold) * mask), jnp.sum(mask)

    if n_chunks > 0:
        h_main = hidden[:, : n_chunks * chunk].reshape(b, n_chunks, chunk, d)
        y_main = labels[:, : n_chunks * chunk].reshape(b, n_chunks, chunk)

        def body(carry, xs):
            h, y = xs  # (b, chunk, d), (b, chunk)
            t, m = chunk_loss(h, y)
            return (carry[0] + t, carry[1] + m), None

        (total, count), _ = jax.lax.scan(
            body,
            (jnp.zeros([], jnp.float32), jnp.zeros([], jnp.float32)),
            (h_main.swapaxes(0, 1), y_main.swapaxes(0, 1)),
            unroll=n_chunks if unroll else 1,
        )
    else:
        total = jnp.zeros([], jnp.float32)
        count = jnp.zeros([], jnp.float32)
    if rem:
        t, m = chunk_loss(hidden[:, n_chunks * chunk :], labels[:, n_chunks * chunk :])
        total = total + t
        count = count + m
    return total / jnp.maximum(count, 1.0)
