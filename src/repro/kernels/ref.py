"""Pure-jnp oracles for every kernel in this package. Tests sweep shapes and
dtypes asserting allclose(kernel(interpret=True), ref)."""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "cac_matmul_ref",
    "cac_train_fwd_ref",
    "cac_train_bwd_ref",
    "bnn_matmul_ref",
    "qnn_matmul_ref",
    "paged_attention_ref",
]


def cac_matmul_ref(x: jax.Array, tau: jax.Array, s: jax.Array) -> jax.Array:
    """Hardware CAC: y[m,n] = sum_k s[k,n] * (+1 if x[m,k] >= tau[k,n] else -1).

    x: (M, K); tau, s: (K, N) -> (M, N) float32. s may contain 0 (padding)."""
    cmp = x[:, :, None] >= tau[None]  # (M, K, N)
    contrib = jnp.where(cmp, s[None], -s[None]).astype(jnp.float32)
    return jnp.sum(contrib, axis=1)


def cac_train_fwd_ref(x: jax.Array, w: jax.Array, beta: jax.Array) -> jax.Array:
    """Training CAC: y[m,n] = sum_k Sign(x[m,k]*w[k,n] + beta[k,n]); Sign(0)=+1."""
    pre = x[:, :, None] * w[None] + beta[None]
    return jnp.sum(jnp.where(pre >= 0, 1.0, -1.0).astype(jnp.float32), axis=1)


def cac_train_bwd_ref(
    x: jax.Array, w: jax.Array, beta: jax.Array, g: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """STE backward (hard-tanh window on the pre-activation):
    mask = 1[|x w + beta| <= 1];
    dx[m,k]    = sum_n g[m,n] mask[m,k,n] w[k,n]
    dw[k,n]    = sum_m g[m,n] mask[m,k,n] x[m,k]
    dbeta[k,n] = sum_m g[m,n] mask[m,k,n]
    """
    pre = x[:, :, None] * w[None] + beta[None]
    mask = (jnp.abs(pre) <= 1.0).astype(jnp.float32)
    gm = g[:, None, :] * mask  # (M, K, N)
    dx = jnp.sum(gm * w[None], axis=2)
    dw = jnp.sum(gm * x[:, :, None], axis=0)
    dbeta = jnp.sum(gm, axis=0)
    return dx, dw, dbeta


def bnn_matmul_ref(x: jax.Array, w: jax.Array) -> jax.Array:
    """XNOR-popcount == matmul of +/-1 values: y = sign(x) @ sign(w)."""
    xs = jnp.where(x >= 0, 1.0, -1.0).astype(jnp.float32)
    ws = jnp.where(w >= 0, 1.0, -1.0).astype(jnp.float32)
    return xs @ ws


def qnn_matmul_ref(
    x_int: jax.Array, w_int: jax.Array, x_scale: float, w_scale: jax.Array
) -> jax.Array:
    """int8 x int8 -> int32 accumulate -> fp32 dequant (per-column w scale)."""
    acc = jnp.matmul(
        x_int.astype(jnp.int32), w_int.astype(jnp.int32), preferred_element_type=jnp.int32
    )
    return acc.astype(jnp.float32) * (w_scale.astype(jnp.float32) * x_scale)


def paged_attention_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    tables: jax.Array,
    q_pos: jax.Array,
    k_scale: jax.Array = None,
    v_scale: jax.Array = None,
    *,
    n_kv_heads: int,
) -> jax.Array:
    """Gather-based oracle for the fused paged-attention kernel: assemble
    each row's logical KV window from the block pool, then one full (not
    online) fp32 softmax under the same ``kv_pos <= q_pos`` mask. Pure XLA,
    so GSPMD partitions it freely — it doubles as the tensor-parallel
    fallback when head counts don't divide the model axis.

    q: (B, C, Hq, D); k/v: one lane-dense pool layer (n_phys, bs, lanes),
    ``n_kv_heads`` heads side by side from lane 0; tables: (B, T) int32;
    q_pos: (B, C) int32; scales: (n_phys, bs, n_kv_heads) f32 for int8 pools.
    """
    b, c, hq, d = q.shape
    bs, hkv = k.shape[1], n_kv_heads
    g = hq // hkv

    def gather(leaf, width):  # (B, T, bs, lanes) -> (B, T*bs, Hkv, width)
        w = leaf[tables][..., :hkv * width]
        return w.reshape(b, w.shape[1] * bs, hkv, width)

    kw, vw = gather(k, d), gather(v, d)
    if k_scale is not None:
        kw = kw.astype(jnp.float32) * gather(k_scale, 1)
        vw = vw.astype(jnp.float32) * gather(v_scale, 1)
    qg = q.astype(jnp.float32).reshape(b, c, hkv, g, d)
    s = jnp.einsum("bchgd,bthd->bchgt", qg, kw.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    s = s / jnp.sqrt(jnp.float32(d))
    kvp = jnp.arange(kw.shape[1], dtype=jnp.int32)
    mask = kvp[None, None] <= q_pos[:, :, None]  # (B, C, T*bs)
    s = jnp.where(mask[:, :, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bchgt,bthd->bchgd", p, vw.astype(jnp.float32))
    return out.reshape(b, c, hq, d).astype(q.dtype)
