"""Pallas TPU kernel for the mamba2 SSD (state-space duality) scan.

The SSD dual form splits the sequence into chunks: within a chunk the
recurrence is a (masked, decay-weighted) quadratic form computed on the MXU;
across chunks a small [state x head_dim] recurrence is carried. On TPU the
chunk axis becomes the sequential grid dimension and the carried state lives
in a VMEM scratch buffer (HBM -> VMEM once per (batch*head)), which is the
TPU-native replacement for the CUDA kernel's shared-memory state.

Layouts (wrapper transposes):
    xbar: [BH, T, hd]   — x * dt, head-major flattened
    la:   [BH, T]       — dt * A (log decay), per head
    B, C: [Bb, T, ns]    — shared across heads (n_groups=1)
Outputs: y [BH, T, hd]; final_state [BH, hd, ns].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(xbar_ref, la_ref, b_ref, c_ref, y_ref, state_ref, s_scratch,
                *, n_chunks):
    c_idx = pl.program_id(1)
    Q = xbar_ref.shape[1]

    @pl.when(c_idx == 0)
    def _init():
        s_scratch[...] = jnp.zeros_like(s_scratch)

    xb = xbar_ref[0].astype(jnp.float32)  # [Q, hd]
    la = la_ref[0].astype(jnp.float32)  # [Q]
    Bm = b_ref[0].astype(jnp.float32)  # [Q, ns]
    Cm = c_ref[0].astype(jnp.float32)  # [Q, ns]

    cum = jnp.cumsum(la)  # inclusive
    total = cum[-1]

    # Intra-chunk quadratic term (MXU): (C B^T ⊙ L) xbar
    G = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [Q, Q]
    diff = cum[:, None] - cum[None, :]
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    tri = col <= row
    L = jnp.exp(jnp.where(tri, diff, -60.0)) * tri  # clamp: no inf*0
    y = jax.lax.dot_general(G * L, xb, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [Q, hd]

    # Inter-chunk term from carried state S [ns, hd].
    S = s_scratch[...]
    y += jnp.exp(cum)[:, None] * jax.lax.dot_general(
        Cm, S, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    # State update: S <- exp(total) S + (B ⊙ w)^T xbar
    w = jnp.exp(total - cum)  # [Q]
    s_new = jnp.exp(total) * S + jax.lax.dot_general(
        Bm * w[:, None], xb, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    s_scratch[...] = s_new
    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(c_idx == n_chunks - 1)
    def _finish():
        state_ref[0] = s_new.T.astype(state_ref.dtype)  # [hd, ns]


def ssd_pallas(xbar, la, B, C, n_heads: int, *, chunk=128, interpret=False):
    """xbar: [BH, T, hd]; la: [BH, T]; B/C: [Bb, T, ns]; T % chunk == 0."""
    BH, T, hd = xbar.shape
    ns = B.shape[-1]
    n_chunks = T // chunk
    h = n_heads

    y, state = pl.pallas_call(
        functools.partial(_ssd_kernel, n_chunks=n_chunks),
        name="ssd",
        grid=(BH, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, hd), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, chunk), lambda bh, c: (bh, c)),
            pl.BlockSpec((1, chunk, ns), lambda bh, c: (bh // h, c, 0)),
            pl.BlockSpec((1, chunk, ns), lambda bh, c: (bh // h, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, hd), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, hd, ns), lambda bh, c: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, hd), xbar.dtype),
            jax.ShapeDtypeStruct((BH, hd, ns), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((ns, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xbar, la, B, C)
    return y, state
