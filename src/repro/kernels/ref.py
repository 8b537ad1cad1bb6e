"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core import hashing

_NEG_INF = -1e30


def hash_to_buckets_ref(keys: jax.Array, table_size: int, seed: int) -> jax.Array:
    """Oracle for the fused murmur+bucket kernel."""
    return hashing.hash_to_buckets(keys, table_size, seed=seed)


def histogram_ref(bins: jax.Array, num_bins: int) -> jax.Array:
    """Oracle for the compare-tile histogram; ids outside [0, num_bins) ignored."""
    b = bins.astype(jnp.int32)
    valid = (b >= 0) & (b < num_bins)
    b = jnp.where(valid, b, 0)
    ones = valid.astype(jnp.int32)
    return jnp.zeros((num_bins,), jnp.int32).at[b.reshape(-1)].add(ones.reshape(-1))


def bucket_probe_ref(
    starts: jax.Array,
    ends: jax.Array,
    q: jax.Array,
    table: jax.Array,
    max_probe: int,
) -> jax.Array:
    """Oracle for the linear bucket scan."""
    n = table.shape[0]
    idx = starts[:, None].astype(jnp.int32) + jnp.arange(max_probe, dtype=jnp.int32)
    valid = idx < ends[:, None]
    vals = table[jnp.clip(idx, 0, n - 1)]
    return jnp.sum(valid & (vals == q[:, None].astype(jnp.uint32)), axis=1).astype(
        jnp.int32
    )


def csr_gather_ref(
    starts: jax.Array,
    counts: jax.Array,
    table: jax.Array,
    capacity: int,
    fill: int = -1,
) -> tuple[jax.Array, jax.Array]:
    """Oracle for the CSR gather kernel: ``(values, row_idx)``, each (capacity,).

    Lane-aware: a multi-column ``(Tn, C)`` table yields ``(capacity, C)``
    values.  Deliberately neither the kernel's per-slot searchsorted nor the
    scatter-and-prefix-sum expansion of ``repro.core.hashgraph.csr_gather``:
    a plain numpy concatenation of the runs, so a bug in either cannot hide
    in the comparison.
    """
    import numpy as np

    starts_n = np.asarray(starts).astype(np.int64)
    counts_n = np.asarray(counts).astype(np.int64)
    table_n = np.asarray(table)
    out_shape = (capacity,) + table_n.shape[1:]
    vals = np.full(out_shape, fill, dtype=np.int32)
    rows = np.full((capacity,), -1, dtype=np.int32)
    pos = 0
    for i, (s, c) in enumerate(zip(starts_n, counts_n)):
        for j in range(c):
            if pos >= capacity:
                break
            vals[pos] = table_n[min(max(s + j, 0), len(table_n) - 1)]
            rows[pos] = i
            pos += 1
    return jnp.asarray(vals), jnp.asarray(rows)


def attention_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    q_heads_per_kv: int = 1,
) -> jax.Array:
    """Oracle attention over (Hq, Sq, D) / (Hkv, Skv, D), f32 internals."""
    hq, sq, d = q.shape
    hkv, skv, _ = k.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if q_heads_per_kv > 1:
        k = jnp.repeat(k, q_heads_per_kv, axis=0)
        v = jnp.repeat(v, q_heads_per_kv, axis=0)
    s = jnp.einsum(
        "hqd,hkd->hqk", q.astype(jnp.float32) * scale, k.astype(jnp.float32)
    )
    q_pos = jnp.arange(sq)[:, None]
    k_pos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        offset = skv - sq
        mask &= k_pos <= q_pos + offset
        if window is not None:
            mask &= k_pos > q_pos + offset - window
    elif window is not None:
        mask &= jnp.abs(k_pos - q_pos) < window
    s = jnp.where(mask[None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked rows produce uniform garbage; zero them like the kernel.
    any_valid = mask.any(axis=1)[None, :, None]
    out = jnp.einsum("hqk,hkd->hqd", p, v.astype(jnp.float32))
    return jnp.where(any_valid, out, 0.0).astype(q.dtype)


def slstm_sequence_ref(pre, r, c0, n0, h0, m0):
    """Oracle for the sLSTM recurrence kernel (lax.scan over time).

    pre (B,H,S,4,hd) f32; r (H,4,hd,hd); state (B,H,hd) each.
    Returns (hs (B,H,S,hd), (c,n,h,m) finals).
    """

    def step(carry, xt):  # xt: (B,H,4,hd)
        c, n, h, m = carry
        rec = jnp.einsum("bhd,hgde->bhge", h, r)
        pre_t = xt + rec
        itil, ftil, ztil, otil = (pre_t[:, :, g] for g in range(4))
        m_new = jnp.maximum(ftil + m, itil)
        i = jnp.exp(itil - m_new)
        f = jnp.exp(ftil + m - m_new)
        z = jnp.tanh(ztil)
        o = jax.nn.sigmoid(otil)
        c2 = f * c + i * z
        n2 = f * n + i
        h2 = o * c2 / jnp.maximum(n2, 1.0)
        return (c2, n2, h2, m_new), h2

    (c, n, h, m), hs = jax.lax.scan(
        step, (c0, n0, h0, m0), pre.transpose(2, 0, 1, 3, 4)
    )
    return hs.transpose(1, 2, 0, 3), (c, n, h, m)
