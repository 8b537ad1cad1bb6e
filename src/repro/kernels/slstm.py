"""Pallas sLSTM recurrence kernel — recurrent weights pinned in VMEM.

The sLSTM step is inherently sequential (h_{t-1} feeds the gates), so the
XLA lowering is a length-S while loop whose body re-reads the per-head
recurrent matrix ``r`` (4·hd² f32 — 4 MB for xlstm-1.3b) from HBM **every
timestep**: 4096 steps × 48 layers × 8 microbatches ≈ 20 PB/device of pure
weight re-reads — the single largest term in the xlstm train_4k roofline.

TPU-native fix (this kernel): grid = (B, H, S/T); the time axis is the
innermost, sequentially-iterated grid dim, state (c, n, h, m) lives in VMEM
scratch across grid steps, and ``r_h`` is loaded ONCE per (b, h) — the
index_map ignores the time index, so Pallas keeps the block resident.
HBM traffic drops to streaming the pre-projected inputs once:
S·4·hd reads + S·hd writes per (b, h).

Validated in interpret mode against the lax.scan oracle
(``repro.models.ssm.slstm_block``) over shape/dtype sweeps.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _kernel(pre_ref, r_ref, c0_ref, n0_ref, h0_ref, m0_ref,
            hs_ref, cf_ref, nf_ref, hf_ref, mf_ref,
            c_s, n_s, h_s, m_s, *, t_block: int, seq_len: int):
    """One (b, h, t_chunk) grid step: ``t_block`` sequential sLSTM steps.

    pre_ref: (1, 1, T, 4, hd) input pre-activations (x·W + b), f32
    r_ref:   (1, 4, hd, hd) recurrent weights — resident across t
    state scratch c/n/h/m: (1, hd) f32, carried across the t grid dim.
    """
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _load_state():
        c_s[...] = c0_ref[0]
        n_s[...] = n0_ref[0]
        h_s[...] = h0_ref[0]
        m_s[...] = m0_ref[0]

    r = r_ref[0]  # (4, hd, hd)

    def step(i, carry):
        c, n, h, m = carry
        xt = pre_ref[0, 0, i]  # (4, hd)
        # recurrent contribution: h (1, hd) × r (4, hd, hd) → (4, hd)
        rec = jax.lax.dot_general(
            h, r, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (1, 4, hd)
        pre = xt[None] + rec  # (1, 4, hd)
        itil = pre[:, 0]
        ftil = pre[:, 1]
        ztil = pre[:, 2]
        otil = pre[:, 3]
        m_new = jnp.maximum(ftil + m, itil)
        ig = jnp.exp(itil - m_new)
        fg = jnp.exp(ftil + m - m_new)
        z = jnp.tanh(ztil)
        o = jax.nn.sigmoid(otil)
        c2 = fg * c + ig * z
        n2 = fg * n + ig
        h2 = o * c2 / jnp.maximum(n2, 1.0)
        hs_ref[0, 0, i] = h2[0]
        # steps beyond the true sequence length (t_block padding) are
        # no-ops on the carried state.
        live = (t * t_block + i) < seq_len
        keep = lambda new, old: jnp.where(live, new, old)
        return keep(c2, c), keep(n2, n), keep(h2, h), keep(m_new, m)

    carry = (c_s[...], n_s[...], h_s[...], m_s[...])
    c, n, h, m = jax.lax.fori_loop(0, t_block, step, carry)
    c_s[...], n_s[...], h_s[...], m_s[...] = c, n, h, m

    @pl.when(t == pl.num_programs(2) - 1)
    def _store_state():
        cf_ref[0] = c_s[...]
        nf_ref[0] = n_s[...]
        hf_ref[0] = h_s[...]
        mf_ref[0] = m_s[...]


def slstm_sequence(
    pre: jax.Array,  # (B, H, S, 4, hd) f32 pre-activations (x·W_in + b)
    r: jax.Array,  # (H, 4, hd, hd) f32 recurrent weights
    c0: jax.Array,  # (B, H, hd) f32
    n0: jax.Array,
    h0: jax.Array,
    m0: jax.Array,
    *,
    t_block: int = 256,
    seq_len: Optional[int] = None,
    interpret: bool = False,
):
    """Run the sLSTM recurrence. Returns (hs (B,H,S,hd), (c,n,h,m) finals).

    ``seq_len``: true length when the time axis carries t_block padding.
    """
    b, h, s, four, hd = pre.shape
    assert four == 4 and s % t_block == 0, (pre.shape, t_block)
    seq_len = seq_len if seq_len is not None else s
    grid = (b, h, s // t_block)
    out_shape = (
        jax.ShapeDtypeStruct((b, h, s, hd), jnp.float32),
        jax.ShapeDtypeStruct((b, h, hd), jnp.float32),
        jax.ShapeDtypeStruct((b, h, hd), jnp.float32),
        jax.ShapeDtypeStruct((b, h, hd), jnp.float32),
        jax.ShapeDtypeStruct((b, h, hd), jnp.float32),
    )
    state_spec = pl.BlockSpec(
        (1, 1, hd), lambda i, j, t: (i, j, 0), memory_space=pltpu.VMEM
    )
    outs = pl.pallas_call(
        partial(_kernel, t_block=t_block, seq_len=seq_len),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, 1, t_block, 4, hd),
                lambda i, j, t: (i, j, t, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            # r: index_map ignores t — resident across the time loop.
            pl.BlockSpec(
                (1, 4, hd, hd), lambda i, j, t: (j, 0, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            state_spec, state_spec, state_spec, state_spec,
        ],
        out_specs=(
            pl.BlockSpec(
                (1, 1, t_block, hd),
                lambda i, j, t: (i, j, t, 0),
                memory_space=pltpu.VMEM,
            ),
            state_spec, state_spec, state_spec, state_spec,
        ),
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((1, hd), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="slstm_recurrence",
    )(pre, r, c0, n0, h0, m0)
    hs, cf, nf, hf, mf = outs
    return hs, (cf, nf, hf, mf)
