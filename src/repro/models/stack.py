"""Generic model assembly: embedding -> scanned block stack -> head.

The layer stack is `pattern` (a tuple of LayerSpecs) repeated
``n_pattern_repeats`` times via lax.scan over stacked params (keeps HLO size
O(len(pattern)) — essential for 100-layer dry-runs), plus an unrolled tail.
Covers decoder-only LMs, mamba2, recurrentgemma, whisper (enc-dec) and
llama-3.2-vision through one code path.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.models import modules
from repro.models.config import LayerSpec, ModelConfig
from repro.models.modules import Policy, RunConfig
from repro.pytree import Param, fan_in_init, merge_params, split_params

# aux-loss keys kept static so scan carries have a fixed tree structure
AUX_KEYS = ("moe_aux_loss", "moe_z_loss")


def _zero_aux(extras=()):
    """Zero aux accumulator. ``extras`` adds fixed-shape keys — e.g. the
    serve EP engine's per-expert routing counts — so the scan carry keeps a
    static tree while layers contribute vector-valued aux entries."""
    z = {k: jnp.zeros((), jnp.float32) for k in AUX_KEYS}
    for k, shape in extras:
        z[k] = jnp.zeros(shape, jnp.float32)
    return z


def _acc_aux(acc, aux):
    # iterate the ACCUMULATOR's keys: layers may emit extra aux entries
    # (they are dropped unless the caller registered them via extras)
    return {k: acc[k] + jnp.asarray(aux.get(k, 0.0), jnp.float32)
            for k in acc}


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_block_stack(key, cfg: ModelConfig, pattern, n_repeats: int):
    """Params for `pattern` scanned n_repeats times: one stacked tree per
    pattern position, leading dim = n_repeats, logical axis 'layers'."""
    out = {}
    for pos, spec in enumerate(pattern):
        keys = jax.random.split(jax.random.fold_in(key, pos), n_repeats)
        template = modules.init_layer(keys[0], cfg, spec)
        _, axes = split_params(template)

        def init_values(k, _spec=spec):
            return split_params(modules.init_layer(k, cfg, _spec))[0]

        values = jax.vmap(init_values)(keys)
        from repro.pytree import prepend_axis
        out[f"pos{pos}"] = merge_params(values, prepend_axis(axes, "layers"))
    return out


def init_model(key, cfg: ModelConfig):
    """Returns a tree of Param (use pytree.split_params before jit)."""
    ks = jax.random.split(key, 8)
    params = {"embed": modules.init_embedding(ks[0], cfg)}

    if cfg.n_pattern_repeats > 0:
        params["blocks"] = _init_block_stack(ks[1], cfg, cfg.pattern,
                                             cfg.n_pattern_repeats)
    for i, spec in enumerate(cfg.tail_specs):
        params[f"tail{i}"] = modules.init_layer(
            jax.random.fold_in(ks[2], i), cfg, spec)

    params["final_norm"] = modules.init_norm(cfg)
    if not cfg.tie_embeddings:
        params["lm_head"] = Param(
            fan_in_init(ks[3], (cfg.vocab_size, cfg.d_model), jnp.float32,
                        fan_in=cfg.d_model), ("vocab", "embed"))

    if cfg.is_encdec:
        enc_spec = LayerSpec(mixer="attn", ffn="dense", causal=False)
        params["encoder"] = {
            "blocks": _init_block_stack(ks[4], cfg, (enc_spec,),
                                        cfg.n_encoder_layers),
            "final_norm": modules.init_norm(cfg),
        }
    if cfg.vision_seq > 0:
        vdim = cfg.vision_dim or cfg.d_model
        params["vision_proj"] = Param(
            fan_in_init(ks[5], (vdim, cfg.d_model), jnp.float32, fan_in=vdim),
            (None, "embed"))
    return params


# ---------------------------------------------------------------------------
# Apply
# ---------------------------------------------------------------------------

def _apply_stack(blocks, tails, cfg: ModelConfig, run: RunConfig, pattern,
                 x, positions, states=None, tail_states=None,
                 encoder_out=None, encoder_positions=None, cache_index=None,
                 layer_override: Optional[Callable] = None,
                 moe_override: Optional[Callable] = None,
                 attend_to_cache: bool = False, page_table=None,
                 aux_extras=(), layer_aux: bool = False):
    """Run the scanned pattern stack + tail. Returns (x, new_states, aux).

    ``aux_extras`` registers extra fixed-shape aux keys (``(key, shape)``
    pairs) accumulated across layers alongside the aux losses. With
    ``layer_aux`` the returned aux dict additionally carries
    ``aux["per_layer"]``: each key stacked per layer row (scan steps first
    — pattern positions within a block summed — then one row per tail),
    collected through the scan's per-step outputs so the carry stays
    fixed-shape. The serve EP engine uses this for per-layer routing
    histograms."""
    aux = _zero_aux(aux_extras)
    decode = states is not None
    layer_rows = None

    def one_block(x, block_params, block_states):
        """Apply all pattern positions once. Returns (x, new_states, aux)."""
        new_states = {}
        a = _zero_aux(aux_extras)
        # sequence-parallel layer boundary (no-op unless act rule 'seq' set)
        x = run.constrain(x, ("batch", "seq", None))
        for pos, spec in enumerate(pattern):
            p = block_params[f"pos{pos}"]
            st = block_states.get(f"pos{pos}") if block_states else None
            if (layer_override is not None and spec.ffn == "moe"
                    and not decode):
                y, laux = layer_override(p, spec, x, positions)
                ns = None
            else:
                y, ns, laux = modules.apply_layer(
                    p, cfg, run, spec, x, positions, state=st,
                    encoder_out=encoder_out,
                    encoder_positions=encoder_positions,
                    cache_index=cache_index, moe_override=moe_override,
                    attend_to_cache=attend_to_cache, page_table=page_table)
            x = y
            a = _acc_aux(a, laux)
            if decode:
                new_states[f"pos{pos}"] = ns
        return x, new_states, a

    if blocks is not None:
        def scan_body(carry, xs):
            x, aux_acc = carry
            bp, bs = xs
            x, ns, a = one_block(x, bp, bs)
            ys = (ns, a) if layer_aux else ns
            return (x, _acc_aux(aux_acc, a)), ys

        if run.remat != "none" and not decode:
            policy = None
            if run.remat == "dots":
                policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            scan_body = jax.checkpoint(scan_body, policy=policy,
                                       prevent_cse=False)

        block_states = states.get("blocks") if decode else None
        if cfg.unroll:
            new_bs, rows = [], []
            carry = (x, aux)
            n_rep = cfg.n_pattern_repeats
            for i in range(n_rep):
                bp = jax.tree.map(lambda v: v[i], blocks)
                bs = (jax.tree.map(lambda v: v[i], block_states)
                      if block_states is not None else None)
                carry, ys = scan_body(carry, (bp, bs))
                if layer_aux:
                    ns, a_i = ys
                    rows.append(a_i)
                else:
                    ns = ys
                new_bs.append(ns)
            (x, aux) = carry
            if layer_aux:
                layer_rows = jax.tree.map(lambda *vs: jnp.stack(vs), *rows)
            new_block_states = (jax.tree.map(
                lambda *vs: jnp.stack(vs), *new_bs) if decode else None)
        else:
            (x, aux), ys = jax.lax.scan(
                scan_body, (x, aux), (blocks, block_states))
            if layer_aux:
                new_block_states, layer_rows = ys
            else:
                new_block_states = ys
    else:
        new_block_states = None

    new_tail_states = []
    for i, (spec, tp) in enumerate(tails):
        st = tail_states[i] if tail_states else None
        x, ns, a = one_block_single(tp, cfg, run, spec, x, positions, st,
                                    encoder_out, encoder_positions,
                                    cache_index, layer_override, decode,
                                    moe_override, attend_to_cache,
                                    page_table)
        aux = _acc_aux(aux, a)
        if layer_aux:
            row = jax.tree.map(lambda v: v[None],
                               _acc_aux(_zero_aux(aux_extras), a))
            layer_rows = row if layer_rows is None else jax.tree.map(
                lambda s, r: jnp.concatenate([s, r], axis=0),
                layer_rows, row)
        new_tail_states.append(ns)

    new_states = None
    if decode:
        new_states = {"blocks": new_block_states, "tails": new_tail_states}
    if layer_aux and layer_rows is not None:
        aux = dict(aux, per_layer=layer_rows)
    return x, new_states, aux


def one_block_single(p, cfg, run, spec, x, positions, st, encoder_out,
                     encoder_positions, cache_index, layer_override, decode,
                     moe_override=None, attend_to_cache=False,
                     page_table=None):
    if layer_override is not None and spec.ffn == "moe" and not decode:
        y, laux = layer_override(p, spec, x, positions)
        return y, None, laux
    return modules.apply_layer(p, cfg, run, spec, x, positions, state=st,
                               encoder_out=encoder_out,
                               encoder_positions=encoder_positions,
                               cache_index=cache_index,
                               moe_override=moe_override,
                               attend_to_cache=attend_to_cache,
                               page_table=page_table)


def apply_model(params, cfg: ModelConfig, run: RunConfig, tokens,
                positions=None, *, decode_state=None, cache_index=None,
                encoder_embeds=None, vision_embeds=None,
                layer_override: Optional[Callable] = None,
                moe_override: Optional[Callable] = None,
                return_hidden: bool = False,
                attend_to_cache: bool = False, page_table=None,
                aux_extras=(), layer_aux: bool = False):
    """Forward pass.

    tokens: [B, S] int32.
    positions: [B, S] (defaults to arange / cache_index).
    decode_state: state tree from init_decode_state (enables KV caching).
    cache_index: scalar next-cache-line index, or per-slot [B] vector
        (continuous batching — each sequence at its own position).
    attend_to_cache: S > 1 prefill attends over the existing cache instead
        of assuming it empty (chunked prefill, DESIGN.md §7).
    page_table: [B, max_pages] int32 — paged-KV mode (DESIGN.md §9); the
        decode_state must come from init_paged_decode_state. Shared by
        every attention layer (one table, per-layer physical pools).
    encoder_embeds: [B, T_enc, d] stub audio-frontend output (whisper).
    vision_embeds: [B, vision_seq, vision_dim] stub patch embeddings (VLM).
    aux_extras / layer_aux: extra fixed-shape aux keys accumulated across
        the stack, optionally also stacked per layer under
        ``aux["per_layer"]`` (see ``_apply_stack``; serve EP histograms).

    Returns (logits [B,S,vocab], new_decode_state, aux).
    """
    B, S = tokens.shape
    pol = run.policy
    if positions is None:
        if cache_index is not None:
            ci = jnp.asarray(cache_index, jnp.int32)
            base = ci[:, None] if ci.ndim == 1 else ci
            positions = jnp.broadcast_to(
                base + jnp.arange(S, dtype=jnp.int32), (B, S))
        else:
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                         (B, S))

    # Cross-attention memory.
    encoder_out = None
    encoder_positions = None
    if cfg.is_encdec:
        assert encoder_embeds is not None, "whisper needs encoder_embeds"
        T_enc = encoder_embeds.shape[1]
        enc_pos = jnp.broadcast_to(jnp.arange(T_enc, dtype=jnp.int32),
                                   (B, T_enc))
        enc_x = encoder_embeds.astype(pol.compute_dtype)
        if "pos" in params["embed"]:
            pe = jnp.take(params["embed"]["pos"], enc_pos[0], axis=0)
            enc_x = enc_x + pe.astype(pol.compute_dtype)[None]
        enc = params["encoder"]
        enc_x, _, _ = _apply_stack(
            enc["blocks"], [], cfg, run,
            (LayerSpec(mixer="attn", ffn="dense", causal=False),),
            enc_x, enc_pos)
        encoder_out = modules.apply_norm(enc["final_norm"], enc_x, pol)
        encoder_positions = enc_pos
    elif cfg.vision_seq > 0:
        assert vision_embeds is not None, "VLM needs vision_embeds"
        encoder_out = (vision_embeds.astype(pol.compute_dtype)
                       @ params["vision_proj"].astype(pol.compute_dtype))
        Tv = encoder_out.shape[1]
        encoder_positions = jnp.broadcast_to(
            jnp.arange(Tv, dtype=jnp.int32), (B, Tv))

    x = modules.apply_embedding(params["embed"], cfg, pol, tokens,
                                positions, run=run)

    tails = [(spec, params[f"tail{i}"])
             for i, spec in enumerate(cfg.tail_specs)]
    tail_states = decode_state["tails"] if decode_state is not None else None
    x, new_state, aux = _apply_stack(
        params.get("blocks"), tails, cfg, run, cfg.pattern, x, positions,
        states=decode_state, tail_states=tail_states,
        encoder_out=encoder_out, encoder_positions=encoder_positions,
        cache_index=cache_index, layer_override=layer_override,
        moe_override=moe_override, attend_to_cache=attend_to_cache,
        page_table=page_table, aux_extras=aux_extras, layer_aux=layer_aux)

    x = modules.apply_norm(params["final_norm"], x, pol)
    if return_hidden:
        return x, new_state, aux
    head = params.get("lm_head")
    logits = modules.apply_unembedding(params["embed"], head, cfg, pol, x)
    logits = run.constrain(logits, ("batch", None, "vocab"))
    return logits, new_state, aux


# ---------------------------------------------------------------------------
# Compute view (serving)
# ---------------------------------------------------------------------------

# Leaves the forward reads only as ``leaf.astype(policy.compute_dtype)``: the
# matmul weights, the embedding table and the head. Every other leaf keeps
# the param dtype, because the forward reads it in f32 or it is small:
#   router                  moe_route computes its logits in the accum dtype
#   scale, bias             norms apply them in the accum dtype
#   q_norm, k_norm          headwise RMSNorm, accum dtype
#   w_i, w_a, b_i, b_a, lam the RG-LRU gates, computed in f32
#   conv_w, conv_b          cast to the activations' dtype, not the policy's
#   A_log, dt_bias, D, norm SSD's decay, step, skip and gated norm (f32 math)
#   xgate, pos              a tanh gate in f32; learned positions (small)
#   eslot                   the EP decode placement map (int32)
COMPUTE_LEAVES = frozenset({
    "wq", "wk", "wv", "wo",                # attention; also the FFNs' "wo"
    "wi_gate", "wi_up", "wi", "bi", "bo",  # dense and expert FFNs
    "table", "lm_head", "vision_proj",     # embedding, head, VLM projector
    "proj_gate", "proj_rec", "out",        # RG-LRU projections
    "in_proj", "out_proj",                 # SSD projections
})


def is_compute_leaf(path, leaf) -> bool:
    """Whether ``compute_view`` holds this leaf (a tree path from
    ``jax.tree_util``) in the compute dtype."""
    key = path[-1] if path else None
    return (isinstance(key, jax.tree_util.DictKey)
            and key.key in COMPUTE_LEAVES
            and jnp.issubdtype(leaf.dtype, jnp.floating))


def compute_view(params, policy: Policy):
    """``params`` with every leaf of ``COMPUTE_LEAVES`` stored in
    ``policy.compute_dtype``: what a serving engine holds. The forward
    gives bit-identical numbers on it, and no step re-reads the f32
    masters to cast them. A leaf already in the compute dtype (a policy
    computing in f32) is returned as it is. Training never takes this
    view: its step updates the f32 masters."""
    cd = jnp.dtype(policy.compute_dtype)
    return jax.tree_util.tree_map_with_path(
        lambda p, v: v.astype(cd) if is_compute_leaf(p, v) else v, params)


# ---------------------------------------------------------------------------
# Decode state
# ---------------------------------------------------------------------------

def init_decode_state(cfg: ModelConfig, batch: int, max_len: int, dtype):
    """Stacked per-layer decode state matching the scan layout."""
    def stacked(spec):
        one = modules.init_layer_state(cfg, spec, batch, max_len, dtype)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.n_pattern_repeats,) + x.shape),
            one)

    state = {}
    if cfg.n_pattern_repeats > 0:
        state["blocks"] = {f"pos{p}": stacked(spec)
                           for p, spec in enumerate(cfg.pattern)}
    else:
        state["blocks"] = None
    state["tails"] = [modules.init_layer_state(cfg, spec, batch, max_len,
                                               dtype)
                      for spec in cfg.tail_specs]
    return state


def init_paged_decode_state(cfg: ModelConfig, batch: int, n_pages: int,
                            page_size: int, dtype):
    """Paged decode state (DESIGN.md §9): per-layer KV pools of ``n_pages``
    shared physical pages (no batch dim) + per-slot recurrent states, in
    the same scan-stacked layout as :func:`init_decode_state`."""
    def stacked(spec):
        one = modules.init_paged_layer_state(cfg, spec, batch, n_pages,
                                             page_size, dtype)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.n_pattern_repeats,) + x.shape),
            one)

    state = {}
    if cfg.n_pattern_repeats > 0:
        state["blocks"] = {f"pos{p}": stacked(spec)
                           for p, spec in enumerate(cfg.pattern)}
    else:
        state["blocks"] = None
    state["tails"] = [modules.init_paged_layer_state(cfg, spec, batch,
                                                     n_pages, page_size,
                                                     dtype)
                      for spec in cfg.tail_specs]
    return state


# -- paged-state tree surgery (engine helpers, DESIGN.md §9.4) --------------
#
# The paged engine splits a decode-state tree into its pooled-KV part
# (shared pages, written by prefill AND decode) and its per-slot recurrent
# part (batch-indexed, inserted on admission like the dense engine). The
# layer dicts are keyed "kv" / "rglru" / "ssd", so the split is a key
# partition applied layer-wise.

def map_layer_states(state, fn):
    """Apply ``fn`` to every per-layer state dict of a decode-state tree."""
    out = {"blocks": None, "tails": [fn(s) for s in state["tails"]]}
    if state["blocks"] is not None:
        out["blocks"] = {k: fn(v) for k, v in state["blocks"].items()}
    return out


def split_kv_state(state):
    """(kv_tree, rec_tree): pooled attention caches vs per-slot recurrent
    states. Both keep the full blocks/tails skeleton (layers without the
    respective part hold empty dicts) so jit signatures stay stable."""
    kv = map_layer_states(
        state, lambda d: {k: v for k, v in d.items() if k == "kv"})
    rec = map_layer_states(
        state, lambda d: {k: v for k, v in d.items() if k != "kv"})
    return kv, rec


def merge_kv_state(kv_tree, rec_tree):
    """Inverse of :func:`split_kv_state` (layer-wise dict union)."""
    out = {"blocks": None,
           "tails": [{**a, **b} for a, b in zip(kv_tree["tails"],
                                                rec_tree["tails"])]}
    if kv_tree["blocks"] is not None:
        out["blocks"] = {k: {**kv_tree["blocks"][k], **rec_tree["blocks"][k]}
                         for k in kv_tree["blocks"]}
    return out


# -- page-granular pool surgery (disaggregated handoff, DESIGN.md §10) ------
#
# The KV handoff between device groups ships a request's ALLOCATED physical
# pages and nothing else: gather pulls exactly the page ids named by the
# source page table out of every layer's pool (page-dim take — the payload
# keeps the [n, page_size, ...] page layout, never a contiguous
# [tokens, ...] cache), and scatter lands them at the destination pool's
# imported page ids. Block leaves carry the scan-stacked layer dim in front
# of the page dim, so the page axis is 1 there and 0 on tails.

def gather_kv_pages(state, page_ids):
    """Pull physical pages ``page_ids`` of every attention layer's pool out
    of a PAGED decode-state tree. Returns the kv skeleton with the page dim
    replaced by ``len(page_ids)`` — the transfer payload."""
    kv, _ = split_kv_state(state)

    def take(axis):
        return lambda v: jnp.take(v, page_ids, axis=axis)

    out = {"blocks": None,
           "tails": [jax.tree.map(take(0), d) for d in kv["tails"]]}
    if kv["blocks"] is not None:
        out["blocks"] = {k: jax.tree.map(take(1), v)
                         for k, v in kv["blocks"].items()}
    return out


def scatter_kv_pages(state, payload, page_ids):
    """Write a :func:`gather_kv_pages` payload into the pool pages
    ``page_ids`` of a PAGED decode-state tree (the import half of the
    handoff). Out-of-range ids (the transfer engine's chunk-padding
    sentinel) are dropped. Returns the full updated state tree; the
    per-slot recurrent part passes through untouched."""
    kv, rec = split_kv_state(state)

    def put(axis):
        def f(dst, src):
            if axis == 0:
                return dst.at[page_ids].set(src.astype(dst.dtype),
                                            mode="drop")
            return dst.at[:, page_ids].set(src.astype(dst.dtype),
                                           mode="drop")
        return f

    new = {"blocks": None,
           "tails": [jax.tree.map(put(0), d, p)
                     for d, p in zip(kv["tails"], payload["tails"])]}
    if kv["blocks"] is not None:
        new["blocks"] = {k: jax.tree.map(put(1), kv["blocks"][k],
                                         payload["blocks"][k])
                         for k in kv["blocks"]}
    return merge_kv_state(new, rec)


def init_paged_prefill_state(cfg: ModelConfig, n_pages: int, page_size: int,
                             dtype):
    """A PAGED prefill state that DETACHES from any serving engine
    (DESIGN.md §10): the per-layer pools plus a batch-1 recurrent carry,
    sized independently of decode-side slot counts. This is what a
    disaggregated PrefillWorker owns — its pool geometry is the prefill
    group's HBM budget, not the decode engine's."""
    return init_paged_decode_state(cfg, 1, n_pages, page_size, dtype)
