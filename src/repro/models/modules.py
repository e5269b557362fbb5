"""Pure-JAX neural-net modules shared by every architecture in the zoo.

Each module is an (init, apply) pair. init returns a tree of
:class:`repro.pytree.Param` (value + logical sharding axes); apply is a pure
function over the value tree. Mixer kinds: full/local attention, RG-LRU
(recurrentgemma), SSD (mamba2). FFN kinds: dense (SwiGLU/GELU) and MoE.

The attention and MoE "parts" are exposed separately (``apply_mixer_part`` /
``apply_ffn_part``) so the zebra-parallelism engine can disaggregate and
pipeline them across device groups.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.models.config import LayerSpec, ModelConfig
from repro.obs import trace as obs_trace
from repro.pytree import (Param, fan_in_init, ones_init, zeros_init)

# ---------------------------------------------------------------------------
# Runtime policy
# ---------------------------------------------------------------------------

_BIG_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)


@dataclasses.dataclass(frozen=True)
class Policy:
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    accum_dtype: Any = jnp.float32  # norms / softmax / router / losses


def _no_constraint(x, axes):
    del axes
    return x


@dataclasses.dataclass(frozen=True)
class RunConfig:
    """Runtime knobs orthogonal to the architecture."""

    policy: Policy = Policy()
    attn_impl: str = "ref"  # ref | chunked | flash (Pallas)
    # MoE execution path. "gather" (default): the single-pack fused
    # ops.moe_ffn pipeline — what every serve/train path runs. "dense":
    # the O(E) every-token-through-every-expert einsum, kept ONLY as the
    # exact test reference that parity suites compare against.
    moe_impl: str = "gather"
    # gather mode: True forces the Pallas grouped kernels (interpret mode
    # off-TPU — test vehicle); False lets kernels/ops pick the backend
    # default (Mosaic on TPU, XLA tile-gather fallback elsewhere).
    use_gmm_kernel: bool = False
    remat: str = "none"  # none | full | dots
    deterministic: bool = True
    chunk_q: int = 512  # query-chunk size of the chunked attention path
    # Embedding lookup strategy: "sharded" gathers against the vocab-sharded
    # f32 table (GSPMD masked-gather + f32 all-reduce over the vocab axis);
    # "replicated" all-gathers the table ONCE in bf16 (1-2 GB for 128k
    # vocabs) and gathers locally — cheaper in both HBM and ICI bytes.
    embed_mode: str = "sharded"
    # Activation-sharding constrainer (sharding.rules.make_constrainer);
    # identity outside a mesh context.
    constrain: Any = _no_constraint


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_norm(cfg: ModelConfig, dim: int | None = None):
    dim = dim or cfg.d_model
    if cfg.norm == "layernorm":
        return {
            "scale": Param(jnp.ones((dim,), jnp.float32), ("embed",)),
            "bias": Param(jnp.zeros((dim,), jnp.float32), ("embed",)),
        }
    return {"scale": Param(jnp.ones((dim,), jnp.float32), ("embed",))}


def apply_norm(params, x, policy: Policy, eps: float = 1e-6):
    xf = x.astype(policy.accum_dtype)
    if "bias" in params:  # layernorm
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * params["scale"].astype(policy.accum_dtype) \
            + params["bias"].astype(policy.accum_dtype)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps)
        y = y * params["scale"].astype(policy.accum_dtype)
    return y.astype(policy.compute_dtype)


def rms_norm_headwise(scale, x, policy: Policy, eps: float = 1e-6):
    """Per-head RMSNorm over the trailing head_dim (qk_norm)."""
    xf = x.astype(policy.accum_dtype)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(ms + eps) * scale.astype(policy.accum_dtype)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def apply_rope(x, positions, theta: float):
    """x: [..., S, n_heads, head_dim]; positions: [..., S] int32."""
    if theta <= 0:
        return x
    head_dim = x.shape[-1]
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # [..., S, half]
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return rotated.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------

def init_embedding(key, cfg: ModelConfig):
    params = {
        "table": Param(
            fan_in_init(key, (cfg.vocab_size, cfg.d_model), jnp.float32,
                        fan_in=cfg.d_model),
            ("vocab", "embed"),
        )
    }
    if cfg.learned_pos:  # learned absolute positions (whisper)
        params["pos"] = Param(
            fan_in_init(jax.random.fold_in(key, 1),
                        (cfg.max_seq_len, cfg.d_model), jnp.float32,
                        fan_in=cfg.d_model),
            (None, "embed"),
        )
    return params


def apply_embedding(params, cfg: ModelConfig, policy: Policy, tokens,
                    positions=None, run: "RunConfig" = None):
    table = params["table"]
    if run is not None and run.embed_mode == "replicated":
        table = run.constrain(table.astype(policy.compute_dtype),
                              (None, None))
    x = jnp.take(table, tokens, axis=0).astype(policy.compute_dtype)
    if cfg.emb_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), policy.compute_dtype)
    if "pos" in params and positions is not None:
        pe = jnp.take(params["pos"], positions, axis=0)
        x = x + pe.astype(policy.compute_dtype)
    if run is not None:
        x = run.constrain(x, ("batch", None, None))
    return x


def apply_unembedding(params, head, cfg: ModelConfig, policy: Policy, x):
    """x: [..., d_model] -> logits [..., vocab] in accum dtype."""
    table = head if head is not None else params["table"]
    return jnp.einsum("...d,vd->...v", x, table.astype(policy.compute_dtype),
                      preferred_element_type=policy.accum_dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, cross: bool = False):
    d, h, kh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    params = {
        "wq": Param(fan_in_init(k1, (d, h * hd), jnp.float32, fan_in=d),
                    ("embed", "q_heads")),
        "wk": Param(fan_in_init(k2, (d, kh * hd), jnp.float32, fan_in=d),
                    ("embed", "kv_heads")),
        "wv": Param(fan_in_init(k3, (d, kh * hd), jnp.float32, fan_in=d),
                    ("embed", "kv_heads")),
        "wo": Param(fan_in_init(k4, (h * hd, d), jnp.float32, fan_in=h * hd),
                    ("q_heads", "embed")),
    }
    if cfg.qk_norm and not cross:
        params["q_norm"] = Param(jnp.ones((hd,), jnp.float32), (None,))
        params["k_norm"] = Param(jnp.ones((hd,), jnp.float32), (None,))
    return params


def attention_mask(q_pos, kv_pos, causal: bool, window: int):
    """Boolean mask [..., S_q, S_kv]: True = attend."""
    q = q_pos[..., :, None]
    k = kv_pos[..., None, :]
    mask = jnp.ones(jnp.broadcast_shapes(q.shape, k.shape), bool)
    if causal:
        mask &= k <= q
    if window > 0:
        mask &= (q - k) < window
    mask &= k >= 0  # entries with negative positions = unwritten cache slots
    return mask


def chunked_attention(q, k, v, q_pos, kv_pos, *, causal: bool, window: int,
                      scale: float, softcap: float, policy: Policy,
                      chunk_q: int = 512, unroll: bool = False):
    """Flash-equivalent pure-jnp attention: scan over query chunks, per-chunk
    structural masking, rematerialized backward. Never materializes the full
    [S, T] score matrix or mask — the CPU/dry-run stand-in for the Pallas
    flash kernel with the same memory behaviour.

    q: [B,S,H,hd]; k/v: [B,T,KH,hd]; q_pos: [B,S]; kv_pos: [B,T].
    """
    B, S, H, hd = q.shape
    T, KH = k.shape[1], k.shape[2]
    G = H // KH
    cq = min(chunk_q, S)
    pad = (-S) % cq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad)), constant_values=-1)
    nq = (S + pad) // cq
    qc = jnp.moveaxis(q.reshape(B, nq, cq, H, hd), 1, 0)
    pc = jnp.moveaxis(q_pos.reshape(B, nq, cq), 1, 0)

    def block(qb, qp, kb, vb, kvp):
        # qb: [B,cq,H,hd]; qp: [B,cq]; kb/vb: [B,t,KH,hd]
        qf = qb.reshape(B, cq, KH, G, hd)
        logits = jnp.einsum("bskgh,btkh->bkgst", qf, kb,
                            preferred_element_type=jnp.float32) * scale
        if softcap > 0:
            logits = softcap * jnp.tanh(logits / softcap)
        m = attention_mask(qp, kvp, causal, window)
        m &= qp[..., :, None] >= 0
        logits = jnp.where(m[:, None, None, :, :], logits, _BIG_NEG)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bkgst,btkh->bskgh",
                         probs.astype(policy.compute_dtype), vb)
        return out.reshape(B, cq, H, hd)

    block = jax.checkpoint(block)  # recompute scores in backward (flash-like)
    if nq == 1:
        o = block(qc[0], pc[0], k, v, kv_pos)[None]
    elif unroll:
        # Static per-chunk KV cropping (the jnp mirror of the flash kernel's
        # causal/window block skipping). Valid because the structural path
        # always runs with positions == arange.
        outs = []
        for i in range(nq):
            lo, hi = 0, T
            if causal:
                hi = min(T, (i + 1) * cq)
            if window > 0:
                lo = max(0, i * cq - window)
            outs.append(block(qc[i], pc[i], k[:, lo:hi], v[:, lo:hi],
                              kv_pos[:, lo:hi]))
        o = jnp.stack(outs)
    else:
        o = jax.lax.map(lambda args: block(*args, k, v, kv_pos), (qc, pc))
    o = jnp.moveaxis(o, 0, 1).reshape(B, nq * cq, H, hd)
    return o[:, :S]


def ref_attention(q, k, v, mask, scale: float, softcap: float, policy: Policy):
    """GQA attention oracle. q: [B,S,H,hd], k/v: [B,T,KH,hd], mask [B,S,T]|[S,T]."""
    B, S, H, hd = q.shape
    KH = k.shape[2]
    G = H // KH
    qf = q.reshape(B, S, KH, G, hd)
    logits = jnp.einsum("bskgh,btkh->bkgst", qf, k,
                        preferred_element_type=jnp.float32) * scale
    if softcap > 0:
        logits = softcap * jnp.tanh(logits / softcap)
    if mask.ndim == 2:
        mask = mask[None]
    logits = jnp.where(mask[:, None, None, :, :], logits, _BIG_NEG)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", probs.astype(policy.compute_dtype), v)
    return out.reshape(B, S, H, hd)


def _attention_inner(q, k, v, cfg: ModelConfig, run: RunConfig, *,
                     positions, kv_pos, causal: bool, window: int,
                     structural: bool):
    """Dispatch to flash kernel / chunked-jnp / materialized reference."""
    scale = cfg.head_dim ** -0.5
    softcap = cfg.attn_logit_softcap
    if structural and run.attn_impl == "flash":
        from repro.kernels import ops as kops  # lazy: avoid cycles
        return kops.flash_attention(q, k, v, causal=causal, window=window,
                                    scale=scale, softcap=softcap)
    if structural and run.attn_impl == "chunked":
        return chunked_attention(q, k, v, positions, kv_pos, causal=causal,
                                 window=window, scale=scale, softcap=softcap,
                                 policy=run.policy, chunk_q=run.chunk_q,
                                 unroll=cfg.unroll)
    mask = attention_mask(positions, kv_pos, causal=causal, window=window)
    return ref_attention(q, k, v, mask, scale, softcap, run.policy)


def _project_qkv(params, cfg: ModelConfig, run: RunConfig, x, positions,
                 kv=None, kv_positions=None, rope: bool = True):
    """Shared q/k/v projection + qk-norm + rope. Returns (q, k, v, kv_pos)."""
    B, S, _ = x.shape
    h, kh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pol = run.policy
    cd = pol.compute_dtype

    q = (x @ params["wq"].astype(cd)).reshape(B, S, h, hd)
    kv_src = kv if kv is not None else x
    kv_pos = kv_positions if kv_positions is not None else positions
    k = (kv_src @ params["wk"].astype(cd)).reshape(B, -1, kh, hd)
    v = (kv_src @ params["wv"].astype(cd)).reshape(B, -1, kh, hd)
    q = run.constrain(q, ("batch", None, "q_heads", None))
    k = run.constrain(k, ("batch", None, "kv_heads", None))
    v = run.constrain(v, ("batch", None, "kv_heads", None))

    if "q_norm" in params:
        q = rms_norm_headwise(params["q_norm"], q, pol)
        k = rms_norm_headwise(params["k_norm"], k, pol)
    if rope and cfg.rope_theta > 0 and kv is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, kv_pos, cfg.rope_theta)
    return q, k, v, kv_pos


def _apply_attention_paged(params, cfg: ModelConfig, run: RunConfig, x,
                           positions, *, causal: bool, window: int, cache,
                           cache_index, rope: bool, page_table):
    """Paged-cache attention (DESIGN.md §9): scatter this step's K/V through
    the page table into the shared pool, then attend over the slot's pages.

    cache: k/v [P, ps, KH, hd] + pos [P, ps] — the POOL, no batch dim.
    Vector ``cache_index`` = per-slot decode (S == 1); scalar = chunked
    prefill at batch 1 writing lines [offset, offset + S). Key positions
    are computed structurally from the table (never read back from the
    pool), so stale lines of recycled pages sit beyond the new owner's
    causal frontier and are unreachable (§9.2).
    """
    B, S, _ = x.shape
    h, hd = cfg.n_heads, cfg.head_dim
    cd = run.policy.compute_dtype
    q, k, v, _ = _project_qkv(params, cfg, run, x, positions, rope=rope)

    P, ps = cache["k"].shape[0], cache["k"].shape[1]
    MP = page_table.shape[1]
    ptype = cache["pos"].dtype
    if jnp.ndim(cache_index) == 1:
        # Per-slot decode: row b writes line cache_index[b] of its own page
        # run. Dead slots (index < 0) and unallocated table slots map to
        # the out-of-bounds sentinel P and are dropped.
        p = cache_index
        pslot = jnp.minimum(jnp.maximum(p, 0) // ps, MP - 1)
        page = jnp.take_along_axis(page_table, pslot[:, None], axis=1,
                                   mode="clip")[:, 0]
        page = jnp.where((p >= 0) & (page >= 0), page, P)
        line = jnp.where(p >= 0, p % ps, 0)
        ck = cache["k"].at[page, line].set(k[:, 0], mode="drop")
        cv = cache["v"].at[page, line].set(v[:, 0], mode="drop")
        cpos = cache["pos"].at[page, line].set(
            positions[:, 0].astype(ptype), mode="drop")
    else:
        # Chunked prefill at batch 1: per-position scatter through the
        # single request's table (pages need not be physically contiguous).
        lines = cache_index + jnp.arange(S, dtype=jnp.int32)
        pslot = jnp.minimum(lines // ps, MP - 1)
        page = jnp.take(page_table[0], pslot, mode="clip")
        page = jnp.where(page >= 0, page, P)
        ck = cache["k"].at[page, lines % ps].set(k[0], mode="drop")
        cv = cache["v"].at[page, lines % ps].set(v[0], mode="drop")
        cpos = cache["pos"].at[page, lines % ps].set(
            positions[0].astype(ptype), mode="drop")
    new_cache = {"k": ck, "v": cv, "pos": cpos}

    from repro.kernels import ops as kops  # lazy: avoid cycles
    scale = hd ** -0.5
    softcap = cfg.attn_logit_softcap
    if S == 1 and causal and (run.use_gmm_kernel
                              or jax.default_backend() == "tpu"):
        # Block-gathered flash decode over the pool (XLA gather fallback
        # is the use_kernel=False branch inside ops).
        out = kops.paged_decode_attention(
            q[:, 0], ck, cv, page_table, positions[:, 0], scale=scale,
            softcap=softcap, window=window,
            use_kernel=True if run.use_gmm_kernel else None)[:, None]
    else:
        kg, vg, kv_pos = kops.paged_gather_kv(ck, cv, page_table)
        out = _attention_inner(q, kg, vg, cfg, run, positions=positions,
                               kv_pos=kv_pos, causal=causal, window=window,
                               structural=False)
    out = run.constrain(out, ("batch", None, "q_heads", None))
    y = out.reshape(B, S, h * hd) @ params["wo"].astype(cd)
    y = run.constrain(y, ("batch", None, None))
    return y, new_cache


def apply_attention(params, cfg: ModelConfig, run: RunConfig, x, positions,
                    *, causal: bool, window: int = 0, kv=None, kv_positions=None,
                    cache=None, cache_index=None, rope: bool = True,
                    attend_to_cache: bool = False, page_table=None):
    """Full/local/cross attention with optional KV cache (decode).

    x: [B, S, d]; positions: [B, S].
    kv: cross-attention memory [B, T, d] (rope disabled for cross).
    cache: dict(k=[B, C, KH, hd], v=..., pos=[B, C]) -> returns updated cache.
    cache_index: scalar (lockstep decode / prefill offset) or per-slot [B]
        vector (continuous batching, DESIGN.md §7.2): row b writes its own
        cache line at cache_index[b]; rows with negative positions write
        nothing, so dead slots never touch their cache.
    attend_to_cache: with S > 1, attend over the full (just-updated) cache
        instead of assuming it empty — chunked prefill, where earlier
        chunks' keys live in the cache. Unwritten lines (pos == -1) are
        masked out.
    page_table: [B, max_pages] int32 — paged-cache mode (DESIGN.md §9):
        ``cache`` holds the SHARED physical pool (k/v [P, ps, KH, hd],
        pos [P, ps]) and row b's cache line p lives at line p % ps of pool
        page page_table[b, p // ps]. Writes scatter through the table
        (negative positions / unallocated slots drop); attention gathers
        the slot's pages with structurally computed key positions, so
        recycled pages' stale lines stay unreachable. Sliding-window
        layers use the same linear paged layout with the window enforced
        by masking (no ring arithmetic).
    """
    if page_table is not None and cache is not None:
        return _apply_attention_paged(
            params, cfg, run, x, positions, causal=causal, window=window,
            cache=cache, cache_index=cache_index, rope=rope,
            page_table=page_table)
    B, S, _ = x.shape
    h, kh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cd = run.policy.compute_dtype
    q, k, v, kv_pos = _project_qkv(params, cfg, run, x, positions, kv,
                                   kv_positions, rope)

    new_cache = None
    structural = cache is None
    if cache is not None:
        # Ring-buffer cache (window>0) or linear cache. Keys stored post-rope.
        C = cache["k"].shape[1]
        if jnp.ndim(cache_index) == 1:
            # Per-slot positions [B]: each row scatters its single new K/V
            # into its own cache line. Inactive slots carry position -1,
            # which maps to the out-of-bounds sentinel C and is dropped —
            # the write never happens, so freed slots stay inert until the
            # next insert overwrites them wholesale.
            assert S == 1, "per-slot cache_index implies single-token decode"
            slot = (cache_index % C) if window > 0 else cache_index
            slot = jnp.where(cache_index >= 0, slot, C)
            b_ix = jnp.arange(B)
            ck = cache["k"].at[b_ix, slot].set(k[:, 0], mode="drop")
            cv = cache["v"].at[b_ix, slot].set(v[:, 0], mode="drop")
            cpos = cache["pos"].at[b_ix, slot].set(
                positions[:, 0].astype(cache["pos"].dtype), mode="drop")
        elif window > 0 and S >= C:
            # prefill block larger than the ring: only the last C keys
            # survive; place key of position p at ring slot p % C.
            shift = (cache_index + S - C) % C
            ck = jnp.roll(k[:, -C:], shift, axis=1)
            cv = jnp.roll(v[:, -C:], shift, axis=1)
            cpos = jnp.roll(positions[:, -C:].astype(cache["pos"].dtype),
                            shift, axis=1)
        elif window > 0 and S > 1:
            # Chunked prefill into a ring (S < C): per-position modular
            # scatter — a dynamic_update_slice would CLAMP (not wrap) a
            # chunk that crosses the ring edge and corrupt the cache.
            idx = (cache_index + jnp.arange(S)) % C
            ck = cache["k"].at[:, idx].set(k)
            cv = cache["v"].at[:, idx].set(v)
            cpos = cache["pos"].at[:, idx].set(
                positions.astype(cache["pos"].dtype))
        else:
            slot = (cache_index % C) if window > 0 else cache_index
            ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
            cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
            cpos = jax.lax.dynamic_update_slice(
                cache["pos"], positions.astype(cache["pos"].dtype), (0, slot))
        new_cache = {"k": ck, "v": cv, "pos": cpos}
        if S == 1 or attend_to_cache:
            if window > 0 and S > 1:
                # Ring-cache chunked prefill attends BEFORE the write
                # lands: the chunk's own tail evicts ring lines that
                # earlier queries of the same chunk still need (query j
                # sees evicted position p iff j < p's ring successor —
                # the pre-fix approximation dropped those keys). Attention
                # reads the PRE-write ring plus the fresh chunk keys; the
                # window mask trims the union to exactly the right lines,
                # and the write (above) still lands for later chunks.
                k = jnp.concatenate([cache["k"], k], axis=1)
                v = jnp.concatenate([cache["v"], v], axis=1)
                kv_pos = jnp.concatenate(
                    [cache["pos"], positions.astype(cache["pos"].dtype)],
                    axis=1)
            else:
                # decode / linear-cache chunked prefill: attend over the
                # cache contents (earlier chunks included; pos == -1 lines
                # are masked out). Exact: nothing is ever evicted (S == 1
                # writes only the query's own line; a linear cache never
                # wraps).
                k, v, kv_pos = ck, cv, cpos
        else:
            # whole-sequence prefill: the cache is assumed empty at entry,
            # so attention runs structurally over the fresh K/V (never
            # materializing the [S, S] score matrix); the cache write is a
            # side effect.
            structural = True

    out = _attention_inner(
        q, k, v, cfg, run, positions=positions, kv_pos=kv_pos,
        causal=causal and kv is None, window=window, structural=structural)
    out = run.constrain(out, ("batch", None, "q_heads", None))
    y = out.reshape(B, S, h * hd) @ params["wo"].astype(cd)
    y = run.constrain(y, ("batch", None, None))
    return y, new_cache


def init_attention_cache(cfg: ModelConfig, batch: int, max_len: int,
                         window: int, dtype):
    C = min(window, max_len) if window > 0 else max_len
    return {
        "k": jnp.zeros((batch, C, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, C, cfg.n_kv_heads, cfg.head_dim), dtype),
        "pos": jnp.full((batch, C), -1, jnp.int32),
    }


def init_paged_attention_cache(cfg: ModelConfig, n_pages: int,
                               page_size: int, dtype):
    """Shared physical KV pool for ONE attention layer (DESIGN.md §9): no
    batch dim — slots own disjoint page subsets through their page tables.
    Sliding-window layers share the layout (window enforced by masking)."""
    return {
        "k": jnp.zeros((n_pages, page_size, cfg.n_kv_heads, cfg.head_dim),
                       dtype),
        "v": jnp.zeros((n_pages, page_size, cfg.n_kv_heads, cfg.head_dim),
                       dtype),
        "pos": jnp.full((n_pages, page_size), -1, jnp.int32),
    }


# ---------------------------------------------------------------------------
# Dense FFN
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig):
    d, f = cfg.d_model, cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    if cfg.mlp_act == "swiglu":
        return {
            "wi_gate": Param(fan_in_init(k1, (d, f), jnp.float32, fan_in=d),
                             ("embed", "mlp")),
            "wi_up": Param(fan_in_init(k2, (d, f), jnp.float32, fan_in=d),
                           ("embed", "mlp")),
            "wo": Param(fan_in_init(k3, (f, d), jnp.float32, fan_in=f),
                        ("mlp", "embed")),
        }
    return {  # gelu (whisper)
        "wi": Param(fan_in_init(k1, (d, f), jnp.float32, fan_in=d),
                    ("embed", "mlp")),
        "bi": Param(jnp.zeros((f,), jnp.float32), ("mlp",)),
        "wo": Param(fan_in_init(k2, (f, d), jnp.float32, fan_in=f),
                    ("mlp", "embed")),
        "bo": Param(jnp.zeros((d,), jnp.float32), ("embed",)),
    }


def apply_mlp(params, cfg: ModelConfig, run: RunConfig, x):
    cd = run.policy.compute_dtype
    if "wi_gate" in params:
        g = jax.nn.silu(x @ params["wi_gate"].astype(cd))
        u = x @ params["wi_up"].astype(cd)
        h = run.constrain(g * u, ("batch", None, "mlp"))
        return run.constrain(h @ params["wo"].astype(cd),
                             ("batch", None, None))
    h = jax.nn.gelu(x @ params["wi"].astype(cd) + params["bi"].astype(cd))
    h = run.constrain(h, ("batch", None, "mlp"))
    return run.constrain(h @ params["wo"].astype(cd) + params["bo"].astype(cd),
                         ("batch", None, None))


# ---------------------------------------------------------------------------
# MoE FFN
# ---------------------------------------------------------------------------

def init_moe(key, cfg: ModelConfig):
    d, f, e = cfg.d_model, cfg.d_ff_expert, cfg.n_experts
    k0, k1, k2, k3 = jax.random.split(key, 4)
    return {
        "router": Param(fan_in_init(k0, (d, e), jnp.float32, fan_in=d),
                        ("embed", None)),
        "wi_gate": Param(
            jax.vmap(lambda k: fan_in_init(k, (d, f), jnp.float32, fan_in=d))(
                jax.random.split(k1, e)), ("expert", "embed", "mlp")),
        "wi_up": Param(
            jax.vmap(lambda k: fan_in_init(k, (d, f), jnp.float32, fan_in=d))(
                jax.random.split(k2, e)), ("expert", "embed", "mlp")),
        "wo": Param(
            jax.vmap(lambda k: fan_in_init(k, (f, d), jnp.float32, fan_in=f))(
                jax.random.split(k3, e)), ("expert", "mlp", "embed")),
    }


def moe_route(router_w, cfg: ModelConfig, policy: Policy, x2d):
    """Router in f32: returns (weights [T,k], idx [T,k] int32, aux dict)."""
    with obs_trace.scope("router"):
        logits = jnp.einsum("td,de->te", x2d.astype(policy.accum_dtype),
                            router_w.astype(policy.accum_dtype))
        probs = jax.nn.softmax(logits, axis=-1)
        weights, idx = jax.lax.top_k(probs, cfg.top_k)
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        # Switch-style load-balance loss + router z-loss. The assignment
        # fraction f is a histogram of the (non-differentiable) top-k
        # indices: an O(T·k) bincount, not an O(T·E) one_hot.
        T = x2d.shape[0]
        counts = jnp.bincount(idx.reshape(-1), length=cfg.n_experts)
        f = counts.astype(policy.accum_dtype) / (T * cfg.top_k)
        p = jnp.mean(probs, axis=0)
        z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
        aux = {
            "moe_aux_loss":
                cfg.n_experts * jnp.sum(f * p) * cfg.router_aux_coef,
            "moe_z_loss": z * cfg.router_z_coef,
        }
        return weights, idx.astype(jnp.int32), aux


def expert_ffn(wi_gate, wi_up, wo, xs, group_sizes, run: RunConfig,
               row_scales=None):
    """Grouped expert FFN over expert-sorted tokens xs [Tk, d].

    wi_*: [E, d, f]; wo: [E, f, d]; group_sizes: [E] int32.
    row_scales: optional [Tk] per-row combine weights, fused into the
    unpack gather (each output row touched once).

    Single-pack fused pipeline (kernels/ops.moe_ffn): one scatter into the
    tile-aligned packed domain, all three GEMMs there (gate+up fused), one
    gather out, one custom_vjp with activation recompute. use_gmm_kernel
    forces the Pallas grouped kernels; otherwise ops picks the backend
    default (Mosaic on TPU, the XLA tile-gather fallback elsewhere) for
    the same packed-domain pipeline. Decode shapes (M ≲ E·block_m) route
    to the group-dense fallback automatically (DESIGN.md §5.5).
    """
    cd = run.policy.compute_dtype
    from repro.kernels import ops as kops
    return kops.moe_ffn(xs, wi_gate.astype(cd), wi_up.astype(cd),
                        wo.astype(cd), group_sizes, row_scales=row_scales,
                        use_kernel=True if run.use_gmm_kernel else None)


def apply_moe(params, cfg: ModelConfig, run: RunConfig, x):
    """Unsharded MoE block. x: [B, S, d] -> (y, aux)."""
    B, S, d = x.shape
    cd = run.policy.compute_dtype
    x2d = x.reshape(-1, d)
    weights, idx, aux = moe_route(params["router"], cfg, run.policy, x2d)
    T, k = idx.shape

    if run.moe_impl == "dense":
        # Every expert on every token; exact but O(E) compute. TEST
        # REFERENCE ONLY — serve/train paths ride the fused pipeline below
        # (the RunConfig default), which is numerically equivalent
        # (dropless) at O(top_k) compute.
        g = jnp.einsum("td,edf->tef", x2d, params["wi_gate"].astype(cd))
        u = jnp.einsum("td,edf->tef", x2d, params["wi_up"].astype(cd))
        h = jax.nn.silu(g) * u
        y_all = jnp.einsum("tef,efd->ted", h, params["wo"].astype(cd))
        gates = jnp.zeros((T, cfg.n_experts), cd)
        gates = gates.at[jnp.arange(T)[:, None], idx].add(weights.astype(cd))
        y = jnp.einsum("ted,te->td", y_all, gates)
        return y.reshape(B, S, d), aux

    # Dropless gather mode: sort token-copies by expert, grouped matmul.
    # The router combine weight rides into the FFN as a fused row scale,
    # so the unpack gather emits already-weighted rows and the combine is
    # a bare segment-sum (one touch per output row).
    with obs_trace.scope("dispatch"):
        flat_idx = idx.reshape(-1)  # [T*k]
        sort = jnp.argsort(flat_idx)
        tok = sort // k
        xs = jnp.take(x2d, tok, axis=0)
        group_sizes = jnp.bincount(flat_idx,
                                   length=cfg.n_experts).astype(jnp.int32)
        w_sorted = jnp.take(weights.reshape(-1), sort, axis=0).astype(cd)
    with obs_trace.scope("experts"):
        ys = expert_ffn(params["wi_gate"], params["wi_up"], params["wo"], xs,
                        group_sizes, run, row_scales=w_sorted)
    with obs_trace.scope("combine"):
        y = jax.ops.segment_sum(ys, tok, num_segments=T)
    return y.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# RG-LRU recurrent block (recurrentgemma / Griffin)
# ---------------------------------------------------------------------------

def init_rglru(key, cfg: ModelConfig):
    d, w, cw = cfg.d_model, cfg.lru_width, cfg.conv_width
    ks = jax.random.split(key, 6)
    # Lambda init so that a = sigmoid(Lambda)^8 is in (0.9, 0.999) (Griffin).
    u = jax.random.uniform(ks[5], (w,), jnp.float32, 0.9, 0.999)
    lam = jnp.log(u ** (1 / 8) / (1 - u ** (1 / 8)))
    return {
        "proj_gate": Param(fan_in_init(ks[0], (d, w), jnp.float32, fan_in=d),
                           ("embed", "mlp")),
        "proj_rec": Param(fan_in_init(ks[1], (d, w), jnp.float32, fan_in=d),
                          ("embed", "mlp")),
        "conv_w": Param(fan_in_init(ks[2], (cw, w), jnp.float32, fan_in=cw),
                        (None, "mlp")),
        "conv_b": Param(jnp.zeros((w,), jnp.float32), ("mlp",)),
        "w_i": Param(fan_in_init(ks[3], (w, w), jnp.float32, fan_in=w),
                     ("mlp", "mlp_out")),
        "b_i": Param(jnp.zeros((w,), jnp.float32), ("mlp",)),
        "w_a": Param(fan_in_init(ks[4], (w, w), jnp.float32, fan_in=w),
                     ("mlp", "mlp_out")),
        "b_a": Param(jnp.zeros((w,), jnp.float32), ("mlp",)),
        "lam": Param(lam, ("mlp",)),
        "out": Param(fan_in_init(jax.random.fold_in(key, 9), (w, d),
                                 jnp.float32, fan_in=w), ("mlp", "embed")),
    }


def causal_conv1d(x, conv_w, conv_b, state=None):
    """Depthwise causal conv. x: [B, S, C]; conv_w: [W, C]; state: [B, W-1, C]."""
    W = conv_w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], W - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    out = sum(xp[:, i:i + x.shape[1], :] * conv_w[i].astype(x.dtype)
              for i in range(W))
    out = out + conv_b.astype(x.dtype)
    new_state = xp[:, -(W - 1):, :] if W > 1 else pad
    return out, new_state


def _lru_scan(a, gx, h0=None):
    """Linear recurrence h_t = a_t * h_{t-1} + gx_t along axis 1 (f32)."""
    if h0 is not None:
        gx = gx.at[:, 0].add(a[:, 0] * h0)

    def combine(left, right):
        a1, b1 = left
        a2, b2 = right
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a, gx), axis=1)
    return h


def apply_rglru(params, cfg: ModelConfig, run: RunConfig, x, state=None):
    """Griffin recurrent block. x: [B,S,d] -> (y, new_state)."""
    pol = run.policy
    cd = pol.compute_dtype
    gate = jax.nn.gelu(x @ params["proj_gate"].astype(cd))
    gate = run.constrain(gate, ("batch", None, "mlp"))
    h = run.constrain(x @ params["proj_rec"].astype(cd),
                      ("batch", None, "mlp"))
    conv_state = state["conv"] if state is not None else None
    h, new_conv = causal_conv1d(h, params["conv_w"], params["conv_b"],
                                conv_state)
    hf = h.astype(jnp.float32)
    i_gate = jax.nn.sigmoid(hf @ params["w_i"].astype(jnp.float32)
                            + params["b_i"])
    r_gate = jax.nn.sigmoid(hf @ params["w_a"].astype(jnp.float32)
                            + params["b_a"])
    log_a = -8.0 * r_gate * jax.nn.softplus(params["lam"])  # [B,S,w]
    a = jnp.exp(log_a)
    gated = jnp.sqrt(jnp.maximum(1.0 - jnp.square(a), 1e-6)) * (i_gate * hf)
    h0 = state["lru"].astype(jnp.float32) if state is not None else None
    hs = _lru_scan(a, gated, h0)
    y = (hs.astype(cd) * gate) @ params["out"].astype(cd)
    y = run.constrain(y, ("batch", None, None))
    new_state = None
    if state is not None:
        new_state = {"conv": new_conv.astype(state["conv"].dtype),
                     "lru": hs[:, -1].astype(state["lru"].dtype)}
    return y, new_state


def init_rglru_state(cfg: ModelConfig, batch: int, dtype):
    return {
        "conv": jnp.zeros((batch, cfg.conv_width - 1, cfg.lru_width), dtype),
        "lru": jnp.zeros((batch, cfg.lru_width), jnp.float32),
    }


# ---------------------------------------------------------------------------
# SSD block (mamba2)
# ---------------------------------------------------------------------------

def init_ssd(key, cfg: ModelConfig):
    d = cfg.d_model
    din = cfg.ssm_expand * d
    nh, s, cw = cfg.ssm_heads, cfg.ssm_state, cfg.conv_width
    proj_out = 2 * din + 2 * s + nh  # z, x, B, C, dt
    ks = jax.random.split(key, 4)
    a_init = jnp.log(jnp.linspace(1.0, 16.0, nh))  # A in [-16, -1]
    return {
        "in_proj": Param(fan_in_init(ks[0], (d, proj_out), jnp.float32,
                                     fan_in=d), ("embed", "mlp")),
        "conv_w": Param(fan_in_init(ks[1], (cw, din + 2 * s), jnp.float32,
                                    fan_in=cw), (None, "mlp")),
        "conv_b": Param(jnp.zeros((din + 2 * s,), jnp.float32), ("mlp",)),
        "dt_bias": Param(jnp.zeros((nh,), jnp.float32), (None,)),
        "A_log": Param(a_init, (None,)),
        "D": Param(jnp.ones((nh,), jnp.float32), (None,)),
        "norm": Param(jnp.ones((din,), jnp.float32), ("mlp",)),
        "out_proj": Param(fan_in_init(ks[2], (din, d), jnp.float32,
                                      fan_in=din), ("mlp", "embed")),
    }


def apply_ssd(params, cfg: ModelConfig, run: RunConfig, x, state=None):
    """mamba2 SSD mixer. x: [B,S,d] -> (y, new_state)."""
    pol = run.policy
    cd = pol.compute_dtype
    B, S, d = x.shape
    din = cfg.ssm_expand * d
    nh, ns = cfg.ssm_heads, cfg.ssm_state
    hd = din // nh

    zxbcdt = x @ params["in_proj"].astype(cd)
    z = zxbcdt[..., :din]
    xbc = zxbcdt[..., din:din + din + 2 * ns]
    dt_raw = zxbcdt[..., -nh:]

    conv_state = state["conv"] if state is not None else None
    xbc, new_conv = causal_conv1d(xbc, params["conv_w"], params["conv_b"],
                                  conv_state)
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :din].reshape(B, S, nh, hd)
    xs = run.constrain(xs, ("batch", None, "q_heads", None))
    Bm = xbc[..., din:din + ns]
    Cm = xbc[..., din + ns:]

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])  # [nh]

    if state is None:
        from repro.kernels import ops as kops  # lazy
        y, last_state = kops.ssd(xs, dt, A, Bm, Cm, chunk=cfg.ssm_chunk,
                                 use_kernel=run.use_gmm_kernel)
    else:
        from repro.kernels import ref as kref
        y, last_state = kref.ssd_decode_step(
            xs, dt, A, Bm, Cm, state["ssm"].astype(jnp.float32))

    y = y + params["D"].astype(cd)[None, None, :, None] * xs
    y = y.reshape(B, S, din)
    # Gated RMSNorm (mamba2): norm(y * silu(z))
    yf = (y * jax.nn.silu(z)).astype(jnp.float32)
    ms = jnp.mean(jnp.square(yf), axis=-1, keepdims=True)
    yf = yf * jax.lax.rsqrt(ms + 1e-6) * params["norm"]
    out = yf.astype(cd) @ params["out_proj"].astype(cd)
    out = run.constrain(out, ("batch", None, None))

    new_state = None
    if state is not None:
        new_state = {"conv": new_conv.astype(state["conv"].dtype),
                     "ssm": last_state.astype(state["ssm"].dtype)}
    return out, new_state


def init_ssd_state(cfg: ModelConfig, batch: int, dtype):
    din = cfg.ssm_expand * cfg.d_model
    nh, ns = cfg.ssm_heads, cfg.ssm_state
    hd = din // nh
    return {
        "conv": jnp.zeros((batch, cfg.conv_width - 1, din + 2 * ns), dtype),
        "ssm": jnp.zeros((batch, nh, hd, ns), jnp.float32),
    }


# ---------------------------------------------------------------------------
# Transformer layer = mixer + (optional cross-attn) + ffn
# ---------------------------------------------------------------------------

def init_layer(key, cfg: ModelConfig, spec: LayerSpec):
    ks = jax.random.split(key, 4)
    params = {"norm1": init_norm(cfg)}
    if spec.mixer in ("attn", "local_attn"):
        params["mixer"] = init_attention(ks[0], cfg)
    elif spec.mixer == "rglru":
        params["mixer"] = init_rglru(ks[0], cfg)
    elif spec.mixer == "ssd":
        params["mixer"] = init_ssd(ks[0], cfg)
    if spec.cross_attn:
        params["xnorm"] = init_norm(cfg)
        params["xattn"] = init_attention(ks[1], cfg, cross=True)
        # gating scalar for cross-attn residual (llama-3.2-vision style)
        params["xgate"] = Param(jnp.zeros((), jnp.float32), ())
    if spec.ffn != "none":
        params["norm2"] = init_norm(cfg)
        params["ffn"] = (init_moe(ks[2], cfg) if spec.ffn == "moe"
                         else init_mlp(ks[2], cfg))
    return params


def apply_mixer_part(params, cfg: ModelConfig, run: RunConfig, spec: LayerSpec,
                     x, positions, state=None, encoder_out=None,
                     encoder_positions=None, cache_index=None,
                     attend_to_cache: bool = False, page_table=None):
    """Pre-norm mixer + residual (+ cross-attn). Returns (h, new_state)."""
    new_state = dict(state) if state is not None else None
    h = x
    if spec.mixer != "none":
        u = apply_norm(params["norm1"], x, run.policy)
        if spec.mixer in ("attn", "local_attn"):
            window = cfg.window if spec.mixer == "local_attn" else 0
            causal = cfg.causal if spec.causal is None else spec.causal
            cache = state.get("kv") if state is not None else None
            with obs_trace.scope("attention"):
                att, new_kv = apply_attention(
                    params["mixer"], cfg, run, u, positions, causal=causal,
                    window=window, cache=cache, cache_index=cache_index,
                    attend_to_cache=attend_to_cache, page_table=page_table)
            if new_state is not None:
                new_state["kv"] = new_kv
            mixed = att
        elif spec.mixer == "rglru":
            mixed, ns = apply_rglru(params["mixer"], cfg, run, u,
                                    state.get("rglru") if state else None)
            if new_state is not None:
                new_state["rglru"] = ns
        elif spec.mixer == "ssd":
            mixed, ns = apply_ssd(params["mixer"], cfg, run, u,
                                  state.get("ssd") if state else None)
            if new_state is not None:
                new_state["ssd"] = ns
        else:
            raise ValueError(spec.mixer)
        h = x + mixed
    if spec.cross_attn:
        u = apply_norm(params["xnorm"], h, run.policy)
        with obs_trace.scope("attention"):
            xa, _ = apply_attention(params["xattn"], cfg, run, u, positions,
                                    causal=False, kv=encoder_out,
                                    kv_positions=encoder_positions)
        gate = jnp.tanh(params["xgate"]).astype(h.dtype)
        h = h + gate * xa
    return h, new_state


def apply_ffn_part(params, cfg: ModelConfig, run: RunConfig, spec: LayerSpec,
                   h, moe_override: Optional[Callable] = None):
    """Pre-norm FFN + residual. Returns (y, aux)."""
    aux = {}
    if spec.ffn == "none":
        return h, aux
    u = apply_norm(params["norm2"], h, run.policy)
    if spec.ffn == "moe":
        if moe_override is not None:
            f, aux = moe_override(params["ffn"], u)
        else:
            f, aux = apply_moe(params["ffn"], cfg, run, u)
    else:
        f = apply_mlp(params["ffn"], cfg, run, u)
    return h + f, aux


def apply_layer(params, cfg: ModelConfig, run: RunConfig, spec: LayerSpec,
                x, positions, state=None, encoder_out=None,
                encoder_positions=None, cache_index=None,
                moe_override: Optional[Callable] = None,
                attend_to_cache: bool = False, page_table=None):
    h, new_state = apply_mixer_part(
        params, cfg, run, spec, x, positions, state=state,
        encoder_out=encoder_out, encoder_positions=encoder_positions,
        cache_index=cache_index, attend_to_cache=attend_to_cache,
        page_table=page_table)
    y, aux = apply_ffn_part(params, cfg, run, spec, h,
                            moe_override=moe_override)
    return y, new_state, aux


def init_layer_state(cfg: ModelConfig, spec: LayerSpec, batch: int,
                     max_len: int, dtype):
    """Decode-state pytree for one layer (None entries for stateless parts)."""
    state = {}
    if spec.mixer in ("attn", "local_attn"):
        window = cfg.window if spec.mixer == "local_attn" else 0
        state["kv"] = init_attention_cache(cfg, batch, max_len, window, dtype)
    elif spec.mixer == "rglru":
        state["rglru"] = init_rglru_state(cfg, batch, dtype)
    elif spec.mixer == "ssd":
        state["ssd"] = init_ssd_state(cfg, batch, dtype)
    return state


def init_paged_layer_state(cfg: ModelConfig, spec: LayerSpec, batch: int,
                           n_pages: int, page_size: int, dtype):
    """Paged decode-state pytree for one layer (DESIGN.md §9): attention KV
    becomes the SHARED pool (no batch dim); recurrent states stay per-slot
    (they are O(d) per slot — paging buys nothing there)."""
    state = {}
    if spec.mixer in ("attn", "local_attn"):
        state["kv"] = init_paged_attention_cache(cfg, n_pages, page_size,
                                                 dtype)
    elif spec.mixer == "rglru":
        state["rglru"] = init_rglru_state(cfg, batch, dtype)
    elif spec.mixer == "ssd":
        state["ssd"] = init_ssd_state(cfg, batch, dtype)
    return state
