"""Pallas TPU flash attention (forward + backward), GQA-aware.

TPU adaptation of the paper's motivating kernel class: the entire HeterMoE
observation (Fig. 2) is that attention efficiency tracks the availability of
an IO-aware fused kernel per device generation. This is that kernel for the
TPU memory hierarchy: q blocks resident in VMEM, k/v streamed block-by-block
over the sequential grid dimension, online softmax in f32 VREGs, MXU-aligned
128x128 tiles.

Layout contract (wrapper handles transposes/padding):
    q:  [B, H,  Sq, hd]     k/v: [B, KH, Skv, hd]     H = KH * G
Masks are structural (causal and/or sliding window) — arbitrary mask arrays
take the reference path in ops.py.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def _block_mask(q_start, k_start, bq, bk, q_len, kv_len, causal, window):
    """[bq, bk] bool mask for one tile, from global positions."""
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    m = (qpos < q_len) & (kpos < kv_len)
    if causal:
        m &= kpos <= qpos
    if window > 0:
        m &= (qpos - kpos) < window
    return m


def _tile_live(iq, ik, bq, bk, causal, window):
    """Whether tile (iq, ik) can contain any unmasked entry."""
    q_start = iq * bq
    k_start = ik * bk
    live = jnp.bool_(True)
    if causal:
        live &= k_start <= q_start + bq - 1
    if window > 0:
        live &= (q_start - (k_start + bk - 1)) < window
    return live


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_s, l_s, *,
                scale, causal, window, q_len, kv_len, softcap, n_k):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    bq = q_ref.shape[2]
    bk = k_ref.shape[2]

    @pl.when(ik == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _NEG)
        l_s[...] = jnp.zeros_like(l_s)
        acc[...] = jnp.zeros_like(acc)

    @pl.when(_tile_live(iq, ik, bq, bk, causal, window))
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)  # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)  # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        mask = _block_mask(iq * bq, ik * bk, bq, bk, q_len, kv_len,
                           causal, window)
        s = jnp.where(mask, s, _NEG)
        m_prev = m_s[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_s[:, 0] = l_s[:, 0] * alpha + jnp.sum(p, axis=-1)
        acc[...] = acc[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_s[:, 0] = m_new

    @pl.when(ik == n_k - 1)
    def _finish():
        l = l_s[...]  # [bq, 1]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc[...] / denom).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.where(l == 0.0, _NEG, m_s[...] + jnp.log(denom))


def flash_forward(q, k, v, *, scale, causal, window, softcap,
                  q_len=None, kv_len=None, block_q=DEFAULT_BLOCK_Q,
                  block_k=DEFAULT_BLOCK_K, interpret=False):
    """q: [B,H,Sq,hd]; k/v: [B,KH,Skv,hd] (pre-padded to block multiples).

    Returns (o [B,H,Sq,hd], lse [B,H,Sq,1] f32). ``q_len``/``kv_len`` are
    the *true* (unpadded) lengths used for masking; default = padded shapes.
    The trailing unit dim of ``lse`` keeps its block ``(1,1,block_q,1)``
    legal for Mosaic: the last two block dims must be divisible by (8, 128)
    or equal the array's, which a ``(1,1,block_q)`` block over ``[B,H,Sq]``
    is not.
    """
    B, H, Sq, hd = q.shape
    KH, Skv = k.shape[1], k.shape[2]
    G = H // KH
    n_q = Sq // block_q
    n_k = Skv // block_k

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        q_len=q_len or Sq, kv_len=kv_len or Skv, softcap=softcap, n_k=n_k)

    return pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=(B, H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc, *, scale, causal, window, q_len, kv_len, n_k):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    bq = q_ref.shape[2]
    bk = k_ref.shape[2]

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(_tile_live(iq, ik, bq, bk, causal, window))
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]  # [bq, 1]
        delta = delta_ref[0, 0]  # [bq, 1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = _block_mask(iq * bq, ik * bk, bq, bk, q_len, kv_len,
                           causal, window)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ik == n_k - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *,
                scale, causal, window, q_len, kv_len, n_q):
    ik = pl.program_id(2)
    iq = pl.program_id(3)
    bk = k_ref.shape[2]
    bq = q_ref.shape[2]

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(_tile_live(iq, ik, bq, bk, causal, window))
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = _block_mask(iq * bq, ik * bk, bq, bk, q_len, kv_len,
                           causal, window)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)  # [bq, bk]
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(iq == n_q - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def flash_backward(q, k, v, o, lse, do, *, scale, causal, window,
                   q_len=None, kv_len=None, block_q=DEFAULT_BLOCK_Q,
                   block_k=DEFAULT_BLOCK_K, interpret=False):
    """Returns (dq [B,H,Sq,hd], dk, dv [B,KH,Skv,hd])."""
    B, H, Sq, hd = q.shape
    KH, Skv = k.shape[1], k.shape[2]
    G = H // KH
    n_q = Sq // block_q
    n_k = Skv // block_k
    q_len = q_len or Sq
    kv_len = kv_len or Skv
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [B,H,Sq,1]

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          window=window, q_len=q_len, kv_len=kv_len, n_k=n_k),
        name="flash_dq",
        grid=(B, H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dk/dv per *query* head (accumulated over q blocks); grouped-summed to
    # kv heads afterwards. Keeps the sequential dim free of write races.
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          window=window, q_len=q_len, kv_len=kv_len, n_q=n_q),
        name="flash_dkv",
        grid=(B, H, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, ik, iq: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, ik, iq: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, ik, iq: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, ik, iq: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, ik, iq: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, ik, iq: (b, h, iq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, ik, iq: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, ik, iq: (b, h, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Skv, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, H, Skv, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, hd), jnp.float32),
                        pltpu.VMEM((block_k, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dk = dk_h.reshape(B, KH, G, Skv, hd).sum(axis=2).astype(k.dtype)
    dv = dv_h.reshape(B, KH, G, Skv, hd).sum(axis=2).astype(v.dtype)
    return dq, dk, dv
