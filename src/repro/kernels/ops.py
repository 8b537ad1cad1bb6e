"""Public jit'd wrappers around the Pallas kernels.

Each wrapper handles padding/reshaping to the TPU ``(rows, 128)`` lane
layout and runs the kernel in interpret mode on non-TPU backends (the CPU
test runs), natively on a TPU.  The ``csr_gather*`` kernels do not lower
for TPU yet, so the table's read path uses the XLA gather by default
(see ``multi_hashgraph._use_kernel_default``).

``block_rows`` left ``None`` resolves through
:func:`repro.kernels.common.resolve_block_rows` — autotuned winner if the
:mod:`repro.kernels.autotune` cache holds one for the call's (kernel,
backend, width, size) bucket, the ``common.DEFAULT_BLOCK_ROWS`` table
otherwise.  Resolution happens in the un-jitted public wrapper, *before*
the jitted inner function, so the jit cache is keyed on the resolved
integer: loading a new autotune cache changes subsequent calls without
invalidating or poisoning existing compiled programs.  Plans/AOT warmup
(``plans.py``/``warm_server``) trace through these wrappers, so executors
compiled after ``autotune.load_cache()`` bake the tuned shapes in.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import hashing
from repro.kernels import bucket_probe as _probe
from repro.kernels import common
from repro.kernels import flash_attention as _flash
from repro.kernels import histogram as _hist
from repro.kernels import murmur as _murmur
from repro.utils import cdiv

LANES = 128


def _auto(interpret: Optional[bool]) -> bool:
    return common.use_interpret_mode() if interpret is None else interpret


def hash_to_buckets(
    keys: jax.Array,
    table_size: int,
    seed: int = hashing.DEFAULT_SEED,
    *,
    block_rows: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused murmur3+mod of a flat (N,) uint32 key array → (N,) int32."""
    block_rows = common.resolve_block_rows(
        "murmur", block_rows, n=keys.shape[0]
    )
    return _hash_to_buckets_jit(
        keys, table_size, seed, block_rows=block_rows, interpret=interpret
    )


@partial(jax.jit, static_argnames=("table_size", "seed", "block_rows", "interpret"))
def _hash_to_buckets_jit(
    keys: jax.Array,
    table_size: int,
    seed: int,
    *,
    block_rows: int,
    interpret: Optional[bool],
) -> jax.Array:
    n = keys.shape[0]
    padded, _ = common.pad_to_block_1d(keys.astype(jnp.uint32), LANES * block_rows, 0)
    out = _murmur.murmur_bucket_2d(
        common.as_lanes(padded, LANES),
        table_size,
        seed,
        block_rows=block_rows,
        interpret=_auto(interpret),
    )
    return out.reshape(-1)[:n]


def bin_histogram(
    bins: jax.Array,
    num_bins: int,
    *,
    block_rows: Optional[int] = None,
    bin_tile: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Histogram of (N,) int32 bin ids → (num_bins,) int32.

    ``num_bins`` is padded up to a multiple of ``bin_tile`` internally.
    """
    block_rows = common.resolve_block_rows(
        "bin_histogram", block_rows, n=bins.shape[0]
    )
    return _bin_histogram_jit(
        bins, num_bins, block_rows=block_rows, bin_tile=bin_tile, interpret=interpret
    )


@partial(
    jax.jit, static_argnames=("num_bins", "block_rows", "bin_tile", "interpret")
)
def _bin_histogram_jit(
    bins: jax.Array,
    num_bins: int,
    *,
    block_rows: int,
    bin_tile: int,
    interpret: Optional[bool],
) -> jax.Array:
    padded_bins = cdiv(num_bins, bin_tile) * bin_tile
    x, _ = common.pad_to_block_1d(bins.astype(jnp.int32), LANES * block_rows, -1)
    out = _hist.histogram_2d(
        common.as_lanes(x, LANES),
        padded_bins,
        block_rows=block_rows,
        bin_tile=bin_tile,
        interpret=_auto(interpret),
    )
    return out[:num_bins]


def bucket_probe(
    table_keys: jax.Array,
    starts: jax.Array,
    ends: jax.Array,
    queries: jax.Array,
    *,
    max_probe: int = 64,
    block_rows: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Per-query match count by linear bucket scan (paper's query loop)."""
    block_rows = common.resolve_block_rows(
        "bucket_probe", block_rows, n=queries.shape[0]
    )
    return _bucket_probe_jit(
        table_keys,
        starts,
        ends,
        queries,
        max_probe=max_probe,
        block_rows=block_rows,
        interpret=interpret,
    )


@partial(jax.jit, static_argnames=("max_probe", "block_rows", "interpret"))
def _bucket_probe_jit(
    table_keys: jax.Array,
    starts: jax.Array,
    ends: jax.Array,
    queries: jax.Array,
    *,
    max_probe: int,
    block_rows: int,
    interpret: Optional[bool],
) -> jax.Array:
    nq = queries.shape[0]
    blk = LANES * block_rows
    s, _ = common.pad_to_block_1d(starts.astype(jnp.int32), blk, 0)
    e, _ = common.pad_to_block_1d(ends.astype(jnp.int32), blk, 0)  # empty window
    q, _ = common.pad_to_block_1d(queries.astype(jnp.uint32), blk, 0)
    t, _ = common.pad_to_block_1d(table_keys.astype(jnp.uint32), LANES, 0)
    out = _probe.bucket_probe_2d(
        common.as_lanes(s, LANES),
        common.as_lanes(e, LANES),
        common.as_lanes(q, LANES),
        common.as_lanes(t, LANES),
        max_probe=max_probe,
        block_rows=block_rows,
        interpret=_auto(interpret),
    )
    return out.reshape(-1)[:nq]


_INT32_MAX = jnp.iinfo(jnp.int32).max


def csr_gather(
    starts: jax.Array,
    counts: jax.Array,
    table: jax.Array,
    *,
    capacity: int,
    fill: int = -1,
    block_rows: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """CSR match-run compaction (pass 2 of count→prefix-sum→gather retrieval).

    Concatenates ``table[starts[i] : starts[i]+counts[i]]`` row-major into a
    static ``(capacity,)`` buffer.  The prefix sum runs in XLA; the per-slot
    binary-search + gather runs in the Pallas kernel with ``offsets`` /
    ``starts`` / ``table`` resident in VMEM.  Returns
    ``(offsets, row_idx, gathered, num_dropped)`` — the same contract as
    ``repro.core.hashgraph.csr_gather`` (which expands the slots by a
    scatter and a prefix sum, not a search) for 32-bit tables: the kernel
    moves int32 lanes, so a uint32 ``table`` is bitcast through int32 and
    restored on output (``fill`` is likewise reinterpreted, e.g. ``-1`` →
    0xFFFFFFFF); other dtypes are rejected.

    Lane-aware: for a multi-column ``(Tn, C)`` table the kernel resolves the
    per-slot binary search once (column 0); the remaining columns reuse the
    returned row indices with a plain XLA gather, so the bisection cost does
    not scale with ``C``.  ``gathered`` has shape ``(capacity, C)``.
    """
    block_rows = common.resolve_block_rows(
        "csr_gather",
        block_rows,
        n=capacity,
        width=1 if table.ndim == 1 else table.shape[-1],
    )
    return _csr_gather_jit(
        starts,
        counts,
        table,
        capacity=capacity,
        fill=fill,
        block_rows=block_rows,
        interpret=interpret,
    )


@partial(
    jax.jit, static_argnames=("capacity", "fill", "block_rows", "interpret")
)
def _csr_gather_jit(
    starts: jax.Array,
    counts: jax.Array,
    table: jax.Array,
    *,
    capacity: int,
    fill: int,
    block_rows: int,
    interpret: Optional[bool],
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    num_rows = counts.shape[0]
    counts = counts.astype(jnp.int32)
    out_dtype = table.dtype
    if out_dtype == jnp.uint32:
        table = jax.lax.bitcast_convert_type(table, jnp.int32)
    elif out_dtype != jnp.int32:
        raise ValueError(f"csr_gather kernel supports int32/uint32 tables, got {out_dtype}")
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts, dtype=jnp.int32)]
    )
    total = offsets[-1]
    # Offsets padding must exceed every real slot id so the bisection never
    # resolves into it.
    o, _ = common.pad_to_block_1d(offsets, LANES, _INT32_MAX)
    s, _ = common.pad_to_block_1d(starts.astype(jnp.int32), LANES, 0)
    cap_padded = cdiv(capacity, LANES * block_rows) * (LANES * block_rows)
    col0 = table if table.ndim == 1 else table[:, 0]
    t, _ = common.pad_to_block_1d(col0.astype(jnp.int32), LANES, fill)
    vals2d, rows2d = _probe.csr_gather_2d(
        common.as_lanes(o, LANES),
        common.as_lanes(s, LANES),
        common.as_lanes(t, LANES),
        capacity_rows=cap_padded // LANES,
        num_rows=num_rows,
        fill=fill,
        block_rows=block_rows,
        interpret=_auto(interpret),
    )
    row_idx = rows2d.reshape(-1)[:capacity]
    if table.ndim == 1:
        gathered = vals2d.reshape(-1)[:capacity]
    else:
        # Reuse the kernel's row resolution for the remaining columns: the
        # same src = starts[row] + (slot - offsets[row]) arithmetic, one
        # vectorized gather per column.
        slot = jnp.arange(capacity, dtype=jnp.int32)
        valid = row_idx >= 0
        rowc = jnp.clip(row_idx, 0, num_rows - 1)
        src = starts.astype(jnp.int32)[rowc] + (slot - offsets[rowc])
        srcc = jnp.clip(src, 0, table.shape[0] - 1)
        cols = [vals2d.reshape(-1)[:capacity]] + [
            jnp.where(valid, table[srcc, c], jnp.int32(fill))
            for c in range(1, table.shape[1])
        ]
        gathered = jnp.stack(cols, axis=-1)
    if out_dtype == jnp.uint32:
        gathered = jax.lax.bitcast_convert_type(gathered, jnp.uint32)
    num_dropped = jnp.maximum(total - capacity, 0).astype(jnp.int32)
    return jnp.minimum(offsets, capacity), row_idx, gathered, num_dropped


def csr_gather_batched(
    starts: jax.Array,
    counts: jax.Array,
    table: jax.Array,
    *,
    capacity: int,
    fill: int = -1,
    block_rows: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused per-source CSR compaction: S gathers in one kernel launch.

    ``starts``/``counts`` are ``(S, N)`` — one CSR gather problem per source
    row, all reading the shared ``table`` — and every source gets its own
    static ``capacity``-slot output segment.  Equivalent to ``S`` calls of
    :func:`csr_gather` (or a vmap of ``hashgraph.csr_gather``) but with a
    single grid over ``(sources, capacity tiles)`` — the ROADMAP kernel
    fusion of the owner-side per-source loop in distributed retrieval.

    Returns ``(offsets, row_idx, gathered, num_dropped)``: ``offsets``
    ``(S, N+1)`` clamped per source, ``row_idx``/``gathered``
    ``(S, capacity[, C])``, and ``num_dropped`` the () int32 total overflow
    across sources.  Same dtype contract as :func:`csr_gather` (int32 lanes,
    uint32 bitcast through, multi-column tables resolve the bisection once).
    """
    block_rows = common.resolve_block_rows(
        "csr_gather_batched",
        block_rows,
        n=capacity,
        width=1 if table.ndim == 1 else table.shape[-1],
    )
    return _csr_gather_batched_jit(
        starts,
        counts,
        table,
        capacity=capacity,
        fill=fill,
        block_rows=block_rows,
        interpret=interpret,
    )


@partial(
    jax.jit, static_argnames=("capacity", "fill", "block_rows", "interpret")
)
def _csr_gather_batched_jit(
    starts: jax.Array,
    counts: jax.Array,
    table: jax.Array,
    *,
    capacity: int,
    fill: int,
    block_rows: int,
    interpret: Optional[bool],
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    s_dim, num_rows = counts.shape
    counts = counts.astype(jnp.int32)
    out_dtype = table.dtype
    if out_dtype == jnp.uint32:
        table = jax.lax.bitcast_convert_type(table, jnp.int32)
    elif out_dtype != jnp.int32:
        raise ValueError(
            f"csr_gather kernel supports int32/uint32 tables, got {out_dtype}"
        )
    starts = starts.astype(jnp.int32)
    offsets = jnp.concatenate(
        [
            jnp.zeros((s_dim, 1), jnp.int32),
            jnp.cumsum(counts, axis=1, dtype=jnp.int32),
        ],
        axis=1,
    )
    totals = offsets[:, -1]

    def pad_rows(x, fillv):
        n = x.shape[1]
        padded = cdiv(n, LANES) * LANES
        if padded != n:
            x = jnp.pad(x, ((0, 0), (0, padded - n)), constant_values=fillv)
        return x.reshape(s_dim, -1, LANES)

    cap_padded = cdiv(capacity, LANES * block_rows) * (LANES * block_rows)
    col0 = table if table.ndim == 1 else table[:, 0]
    t, _ = common.pad_to_block_1d(col0.astype(jnp.int32), LANES, fill)
    vals3, rows3 = _probe.csr_gather_batched_2d(
        pad_rows(offsets, _INT32_MAX),
        pad_rows(starts, 0),
        common.as_lanes(t, LANES),
        capacity_rows=cap_padded // LANES,
        num_rows=num_rows,
        fill=fill,
        block_rows=block_rows,
        interpret=_auto(interpret),
    )
    row_idx = rows3.reshape(s_dim, -1)[:, :capacity]
    if table.ndim == 1:
        gathered = vals3.reshape(s_dim, -1)[:, :capacity]
    else:
        # Reuse the kernel's row resolution for the remaining columns (same
        # contract as csr_gather, vectorized over the source axis).
        slot = jnp.arange(capacity, dtype=jnp.int32)[None, :]
        valid = row_idx >= 0
        rowc = jnp.clip(row_idx, 0, num_rows - 1)
        src = jnp.take_along_axis(starts, rowc, axis=1) + (
            slot - jnp.take_along_axis(offsets, rowc, axis=1)
        )
        srcc = jnp.clip(src, 0, table.shape[0] - 1)
        cols = [vals3.reshape(s_dim, -1)[:, :capacity]] + [
            jnp.where(valid, table[srcc, c], jnp.int32(fill))
            for c in range(1, table.shape[1])
        ]
        gathered = jnp.stack(cols, axis=-1)
    if out_dtype == jnp.uint32:
        gathered = jax.lax.bitcast_convert_type(gathered, jnp.uint32)
    num_dropped = jnp.sum(jnp.maximum(totals - capacity, 0)).astype(jnp.int32)
    return jnp.minimum(offsets, capacity), row_idx, gathered, num_dropped


def interleave_layer_runs(starts, counts, tables):
    """Slot-major/layer-minor interleave of per-layer CSR run descriptors.

    ``starts``/``counts`` are ``(L, S, N)`` with starts already offset into
    the concatenated layer address space; returns ``(starts_i, counts_i,
    table_cat)`` where the ``(S, N·L)`` descriptors place slot ``i``'s L
    runs adjacently in epoch order.  This packing order is load-bearing —
    the ragged return reconstructs segment offsets from per-slot totals
    assuming exactly it — so both the Pallas path
    (:func:`csr_gather_layers`) and the jnp reference in
    ``multi_hashgraph`` share this one definition.
    """
    l, s_dim, n = counts.shape
    table_cat = tables[0] if l == 1 else jnp.concatenate(tables, axis=0)
    starts_i = starts.astype(jnp.int32).transpose(1, 2, 0).reshape(s_dim, n * l)
    counts_i = counts.astype(jnp.int32).transpose(1, 2, 0).reshape(s_dim, n * l)
    return starts_i, counts_i, table_cat


def csr_gather_layers(
    starts: jax.Array,
    counts: jax.Array,
    tables,
    *,
    capacity: int,
    fill: int = -1,
    block_rows: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused owner-side gather across a layer stack: one launch for L·S CSRs.

    ``starts``/``counts`` are ``(L, S, N)`` — for each of ``L`` layers, one
    CSR gather problem per source device, with ``starts`` already offset
    into the concatenated layer address space — and ``tables`` is the
    per-layer tuple of value tables (``(T_l,)`` or ``(T_l, C)`` int32).
    The per-layer descriptors are interleaved slot-major/layer-minor per
    source (slot ``i``'s L runs are adjacent, epoch order), so each source's
    output segment holds every routed query's *merged* layer runs
    contiguously — exactly the packing a single ragged return trip needs.
    One :func:`csr_gather_batched` grid over ``(sources, capacity tiles)``
    with ``N·L`` rows per source replaces the L separate per-layer launch
    rounds of the unfused path.

    Returns ``(gathered, num_dropped)``: ``(S, capacity[, C])`` packed
    segments and the () int32 total overflow across sources.
    """
    starts_i, counts_i, table_cat = interleave_layer_runs(starts, counts, tables)
    _, _, gathered, num_dropped = csr_gather_batched(
        starts_i,
        counts_i,
        table_cat,
        capacity=capacity,
        fill=fill,
        block_rows=block_rows,
        interpret=interpret,
    )
    return gathered, num_dropped


@partial(
    jax.jit,
    static_argnames=(
        "causal",
        "window",
        "scale",
        "block_q",
        "block_kv",
        "interpret",
    ),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Flash attention over (B, Hq, S, D) with GQA kv (B, Hkv, Skv, D)."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    group = hq // hkv
    qf = q.reshape(b * hq, sq, d)
    kf = k.reshape(b * hkv, skv, d)
    vf = v.reshape(b * hkv, skv, d)
    out = _flash.flash_attention_fhsd(
        qf,
        kf,
        vf,
        causal=causal,
        window=window,
        scale=scale,
        block_q=block_q,
        block_kv=block_kv,
        q_heads_per_kv=group,
        interpret=_auto(interpret),
    )
    return out.reshape(b, hq, sq, d)


@partial(jax.jit, static_argnames=("t_block", "interpret"))
def slstm_recurrence(
    pre: jax.Array,
    r: jax.Array,
    c0: jax.Array,
    n0: jax.Array,
    h0: jax.Array,
    m0: jax.Array,
    *,
    t_block: int = 256,
    interpret: Optional[bool] = None,
):
    """sLSTM recurrence with VMEM-pinned recurrent weights.

    pre (B,H,S,4,hd) f32, r (H,4,hd,hd) f32, state (B,H,hd) f32 each.
    S is padded to a multiple of ``t_block`` internally.
    """
    from repro.kernels import slstm as _slstm

    b, h, s, four, hd = pre.shape
    tb = min(t_block, s)
    pad = (-s) % tb
    if pad:
        pre = jnp.pad(pre, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    hs, finals = _slstm.slstm_sequence(
        pre.astype(jnp.float32),
        r.astype(jnp.float32),
        c0.astype(jnp.float32),
        n0.astype(jnp.float32),
        h0.astype(jnp.float32),
        m0.astype(jnp.float32),
        t_block=tb,
        seq_len=s,
        interpret=_auto(interpret),
    )
    return hs[:, :, :s], finals
