"""Pallas TPU paged decode attention (DESIGN.md §9.3).

Flash-style single-token decode over a PAGED KV cache: the physical pool is
``[n_pages, page_size, KH, hd]`` shared by every slot, and each slot's pages
are block-gathered through a scalar-prefetched page table — the same
prefetched-index contract as ``gmm_glu_tiled``'s ``tile_group`` map, applied
to the sequential kv dimension of a decode flash kernel. One grid step
streams ONE physical page, all KH heads of it, into VMEM (its index computed
from the prefetched table before the body runs, so the DMA pipeline still
runs ahead) and folds it into each head's online softmax.

The pool is viewed as ``[n_pages, page_size, KH * hd]`` so that a page is one
contiguous block whose last two dims are the array's own: Mosaic requires
the last two block dims to be divisible by (8, 128) or to equal the array's,
and a one-head block ``(1, page_size, 1, hd)`` over ``[.., KH, hd]`` is
neither when KH > 1.

Masking is structural (DESIGN.md §9.2): line ``l`` of table slot ``j`` is key
position ``j * page_size + l``; positions beyond the slot's query position
(its causal frontier) are masked, which is also what makes recycled pages'
stale lines unreachable — no per-line validity state is read.

Layout contract (wrapper in ops.py handles padding/reshapes):
    q:     [B, KH, Gp, hdp]   Gp = GQA group padded to sublane multiple
    k/v:   [P, page_size, KH, hdp]
    page_table / page_valid: [B, MP] int32 (prefetched; table pre-clamped)
    q_pos: [B] int32 (the slot's current key-write position; < 0 = dead)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)


def _paged_decode_kernel(pt_ref, valid_ref, qpos_ref, q_ref, k_ref, v_ref,
                         o_ref, acc, m_s, l_s, *, scale, softcap, window,
                         page_size, n_pages_seq, n_kv_heads, head_dim):
    b = pl.program_id(0)
    jp = pl.program_id(1)

    @pl.when(jp == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _NEG)
        l_s[...] = jnp.zeros_like(l_s)
        acc[...] = jnp.zeros_like(acc)

    q_pos = qpos_ref[b]
    live = (valid_ref[b, jp] > 0) & (q_pos >= 0)

    @pl.when(live)
    def _body():
        for h in range(n_kv_heads):
            lanes = slice(h * head_dim, (h + 1) * head_dim)
            q = q_ref[0, h].astype(jnp.float32)          # [Gp, hd]
            k = k_ref[0, :, lanes].astype(jnp.float32)   # [page_size, hd]
            v = v_ref[0, :, lanes].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * scale
            if softcap > 0:
                s = softcap * jnp.tanh(s / softcap)
            # Structural key positions: line l of table slot jp sits at
            # jp * page_size + l. Causal frontier + optional sliding window.
            kpos = jp * page_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            mask = kpos <= q_pos
            if window > 0:
                mask &= (q_pos - kpos) < window
            s = jnp.where(mask, s, _NEG)
            m_prev = m_s[h]                                # [Gp, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_s[h] = l_s[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc[h] = acc[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_s[h] = m_new

    @pl.when(jp == n_pages_seq - 1)
    def _finish():
        l = l_s[...]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc[...] / denom).astype(o_ref.dtype)


def paged_decode_forward(q, k_pool, v_pool, page_table, q_pos, *, scale,
                         softcap=0.0, window=0, interpret=False):
    """q: [B, KH, Gp, hd]; pools: [P, page_size, KH, hd];
    page_table: [B, MP] int32 (-1 = unallocated slot); q_pos: [B] int32.

    Returns [B, KH, Gp, hd] attention output (zeros for dead slots —
    callers mask). The raw table is split into a clamped index array (for
    the BlockSpec index map) plus a validity array (for in-kernel masking);
    both ride the scalar-prefetch channel.
    """
    B, KH, Gp, hd = q.shape
    P, page_size = k_pool.shape[0], k_pool.shape[1]
    MP = page_table.shape[1]

    pt = jnp.maximum(page_table, 0).astype(jnp.int32)
    valid = (page_table >= 0).astype(jnp.int32)
    qp = q_pos.astype(jnp.int32)
    # Free row-major view: one page = one contiguous [page_size, KH*hd] block.
    k_flat = k_pool.reshape(P, page_size, KH * hd)
    v_flat = v_pool.reshape(P, page_size, KH * hd)

    kernel = functools.partial(
        _paged_decode_kernel, scale=float(scale), softcap=float(softcap),
        window=int(window), page_size=page_size, n_pages_seq=MP,
        n_kv_heads=KH, head_dim=hd)

    page_spec = pl.BlockSpec((1, page_size, KH * hd),
                             lambda b, jp, pt, vl, qp: (pt[b, jp], 0, 0))
    q_spec = pl.BlockSpec((1, KH, Gp, hd),
                          lambda b, jp, pt, vl, qp: (b, 0, 0, 0))
    return pl.pallas_call(
        kernel,
        name="paged_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, MP),
            in_specs=[q_spec, page_spec, page_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((KH, Gp, hd), jnp.float32),
                pltpu.VMEM((KH, Gp, 1), jnp.float32),
                pltpu.VMEM((KH, Gp, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KH, Gp, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(pt, valid, qp, q, k_flat, v_flat)
