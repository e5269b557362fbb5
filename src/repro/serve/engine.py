"""Serving engines over sharded KV decode states.

Two entry points:

* ``make_serve_program`` / ``BatchedServer`` — the lockstep demo path: one
  scalar ``cache_index`` shared by the whole batch, whole-batch prefill,
  greedy decode. Kept for A/B parity tests and the lockstep fallback.
* ``make_continuous_program`` / ``ContinuousBatchingEngine`` — the real
  serving path (DESIGN.md §7): per-slot position vector ``[B]`` + active
  mask, chunked prefill into a batch-1 cache that is *inserted* into a
  free slot without touching live ones, sampled decode (temperature /
  top-k / top-p per slot), slot recycling on EOS or length limit.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import stack
from repro.models.config import ModelConfig, ShapeConfig
from repro.obs import registry as obs_registry
from repro.obs import trace as obs_trace
from repro.models.modules import RunConfig
from repro.serve import sampling
from repro.serve.metrics import ServeMetrics
from repro.serve.scheduler import PrefillChunk, Request, Scheduler
from repro.sharding.rules import (ShardingRules, rules_for,
                                  slot_vector_spec)
from repro.train.step import abstract_params, fit_batch_axes


def _state_spec_for(cfg: ModelConfig, mesh: Mesh, b, kv_bodies):
    """Shared decode-state leaf-spec mapper.

    Recurrent leaves (conv/lru/ssm) have ONE mapping — batch over "data",
    channel/head dims over "model" — used by both the dense and the paged
    state trees; only the attention-cache leaves (k/v/pos) differ, so the
    caller passes their bodies via ``kv_bodies(tail)`` (per-slot dense
    caches vs shared paged pools). ``b`` is the fitted batch-axis tuple.
    """
    from repro.sharding.rules import fit_spec
    mdl = "model"

    def spec_for(name: str, leaf) -> P:
        stacked = leaf.ndim and leaf.shape[0] == cfg.n_pattern_repeats \
            and cfg.n_pattern_repeats > 1
        lead = (None,) if stacked else ()
        tail = name.rsplit("/", 1)[-1]
        if tail in ("k", "v", "pos"):
            body = (*lead, *kv_bodies(tail, leaf.ndim - len(lead)))
        else:
            body = {
                "conv": (*lead, b, None, mdl),
                "lru": (*lead, b, mdl),
                "ssm": (*lead, b, mdl, None, None),
            }.get(tail, (*lead, *([None] * (leaf.ndim - len(lead)))))
        return fit_spec(leaf.shape, mesh, body)

    return spec_for


def decode_state_specs(cfg: ModelConfig, mesh: Mesh, rules: ShardingRules,
                       batch: int, max_len: int, dtype=jnp.bfloat16):
    """PartitionSpecs for the decode-state tree (by leaf role).

    KV caches shard the *sequence* dim over "model" (flash-decoding style:
    kv-head counts rarely divide the TP axis, sequence always does at these
    lengths) plus batch over "data"; recurrent states shard their channel /
    head dims over "model"."""
    baxes = fit_batch_axes(batch, mesh, rules.batch_axes)
    b = baxes if baxes else None
    mdl = "model"
    kv = {"k": (b, mdl, None, None), "v": (b, mdl, None, None),
          "pos": (b, mdl)}
    spec_for = _state_spec_for(cfg, mesh, b, lambda tail, nd: kv[tail])

    state_shapes = jax.eval_shape(
        lambda: stack.init_decode_state(cfg, batch, max_len, dtype))
    from repro.pytree import tree_map_with_path_names
    return state_shapes, tree_map_with_path_names(spec_for, state_shapes)


def compute_params(program, params):
    """The parameters a serving engine holds: ``stack.compute_view`` of
    ``params``, made once by one program whose outputs take the serving
    program's param shardings, so that no prefill or decode step casts a
    weight again. A tree with nothing to cast (a policy computing in f32,
    or a view already) is only placed. The f32 masters stay the caller's:
    an engine never writes its view back as weights.

    Sets the gauges ``serve.params_bytes.compute`` (bytes of the leaves
    the view holds for the matmuls) and ``serve.params_bytes.master``
    (bytes of every other leaf) in ``obs.registry.PROCESS``."""
    policy = program.run.policy
    cd = jnp.dtype(policy.compute_dtype)
    leaves = jax.tree_util.tree_leaves_with_path(params)
    with program.mesh:
        if any(stack.is_compute_leaf(p, v) and v.dtype != cd
               for p, v in leaves):
            params = jax.jit(
                functools.partial(stack.compute_view, policy=policy),
                out_shardings=program.param_shardings)(params)
        else:
            params = jax.device_put(params, program.param_shardings)
    leaves = jax.tree_util.tree_leaves_with_path(params)
    compute = sum(v.nbytes for p, v in leaves if stack.is_compute_leaf(p, v))
    obs_registry.PROCESS.set("serve.params_bytes.compute", compute)
    obs_registry.PROCESS.set("serve.params_bytes.master",
                             sum(v.nbytes for _, v in leaves) - compute)
    return params


@dataclasses.dataclass
class ServeProgram:
    cfg: ModelConfig
    run: RunConfig
    mesh: Mesh
    prefill_step: Callable  # (params, tokens, state, **fronts) -> (state, logits)
    decode_step: Callable   # (params, state, tok, idx, **fronts) -> (state, tok)
    state_shapes: object
    state_shardings: object
    param_shardings: object
    batch_sharding: object


def make_serve_program(cfg: ModelConfig, mesh: Mesh, run: RunConfig,
                       shape: ShapeConfig,
                       max_len: Optional[int] = None) -> ServeProgram:
    rules = rules_for(cfg, mesh, variant="serve")
    max_len = max_len or shape.seq_len
    B = shape.global_batch
    from repro.sharding.rules import fitted_shardings
    pshapes, paxes = abstract_params(cfg)
    psh = fitted_shardings(pshapes, paxes, rules, mesh)
    state_shapes, sspecs = decode_state_specs(cfg, mesh, rules, B, max_len,
                                              run.policy.compute_dtype)
    ssh = jax.tree.map(lambda s: NamedSharding(mesh, s), sspecs,
                       is_leaf=lambda x: isinstance(x, P))
    baxes = fit_batch_axes(B, mesh, rules.batch_axes)
    bsh = NamedSharding(mesh, P(baxes if baxes else None))
    from repro.sharding.rules import make_constrainer
    act_rules = dataclasses.replace(rules, batch_axes=baxes)
    run = dataclasses.replace(run, constrain=make_constrainer(act_rules, mesh))

    front_sh = {}
    if cfg.is_encdec:
        front_sh["encoder_embeds"] = NamedSharding(
            mesh, P(baxes if baxes else None, None, None))
    if cfg.vision_seq > 0:
        front_sh["vision_embeds"] = NamedSharding(
            mesh, P(baxes if baxes else None, None, None))

    # MoE FFNs always go through the sharded EP path in serving (the gather
    # path would let GSPMD replicate expert weights across the pod).
    moe_override = None
    if cfg.is_moe:
        from repro.core.zebra_spmd import ZebraConfig, make_ep_moe
        zc = ZebraConfig(mode="replicated", batch_axes=baxes or ("data",),
                         capacity_factor=cfg.capacity_factor * 2)
        moe_fn = make_ep_moe(mesh, cfg, run, zc)

        def moe_override(ffn_params, u):
            y2, aux = moe_fn(ffn_params, u.reshape(-1, u.shape[-1]))
            return y2.reshape(u.shape).astype(u.dtype), aux

    def prefill(params, state, tokens, fronts):
        """Full-sequence prefill writing the KV caches; returns last logits.
        Only the final position is unembedded ([B,S,V] f32 logits would be
        tens of GB at 32k)."""
        from repro.models import modules
        hidden, state, _ = stack.apply_model(
            params, cfg, run, tokens, decode_state=state,
            cache_index=jnp.zeros((), jnp.int32), moe_override=moe_override,
            return_hidden=True, **fronts)
        last = modules.apply_unembedding(
            params["embed"], params.get("lm_head"), cfg, run.policy,
            hidden[:, -1])
        return state, last

    def decode(params, state, tok, cache_index, fronts):
        """One decode step: tok [B,1] -> greedy next token [B,1]."""
        logits, state, _ = stack.apply_model(
            params, cfg, run, tok, decode_state=state,
            cache_index=cache_index, moe_override=moe_override, **fronts)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return state, nxt[:, None]

    jit_prefill = jax.jit(prefill, in_shardings=(psh, ssh, bsh, front_sh),
                          out_shardings=(ssh, None), donate_argnums=(1,))
    jit_decode = jax.jit(decode, in_shardings=(psh, ssh, bsh, None, front_sh),
                         out_shardings=(ssh, None), donate_argnums=(1,))

    return ServeProgram(cfg=cfg, run=run, mesh=mesh,
                        prefill_step=jit_prefill, decode_step=jit_decode,
                        state_shapes=state_shapes, state_shardings=ssh,
                        param_shardings=psh, batch_sharding=bsh)


class BatchedServer:
    """Minimal continuous-batching loop over fixed slots (example driver)."""

    def __init__(self, program: ServeProgram, params, batch: int,
                 max_len: int):
        self.p = program
        self.params = compute_params(program, params)
        self.batch = batch
        self.max_len = max_len
        cfg, run = program.cfg, program.run
        with program.mesh:
            self.state = jax.jit(
                lambda: stack.init_decode_state(cfg, batch, max_len,
                                                run.policy.compute_dtype),
                out_shardings=program.state_shardings)()
        self.cache_index = jnp.zeros((), jnp.int32)
        self.tokens = jnp.zeros((batch, 1), jnp.int32)

    def submit_prefill(self, tokens, fronts=None):
        with self.p.mesh:
            self.state, last = self.p.prefill_step(self.params, self.state,
                                                   tokens, fronts or {})
        self.cache_index = jnp.asarray(tokens.shape[1], jnp.int32)
        self.tokens = jnp.argmax(last, axis=-1).astype(jnp.int32)[:, None]
        return self.tokens

    def step(self, fronts=None):
        with self.p.mesh:
            self.state, self.tokens = self.p.decode_step(
                self.params, self.state, self.tokens, self.cache_index,
                fronts or {})
        self.cache_index = self.cache_index + 1
        return self.tokens


# ---------------------------------------------------------------------------
# Continuous batching (DESIGN.md §7)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ContinuousProgram:
    """Compiled pieces of the continuous-batching engine.

    Two builds share this container (DESIGN.md §7 / §9):

    * dense (``paged=False``): per-slot contiguous KV reservations; prefill
      runs on a separate batch-1 state inserted wholesale on admission.
    * paged (``paged=True``): KV lives in shared physical pools addressed
      through per-slot page tables; prefill writes its pages DIRECTLY into
      the pool (pages are disjoint from live slots'), so the insert step
      copies only the batch-1 recurrent carry and slot recycling is a
      host-side page-table reset. Step signatures:
        prefill_step(params, state, prec, tokens[1,c], offset, ptrow[1,MP])
            -> (state, prec, last_logits)
        insert_step(state, prec, slot) -> state
        decode_step(params, state, tok, pos, ptabs[B,MP], active, rids,
                    ngen, temp, topk, topp) -> (state, next, last_logits)
    """

    cfg: ModelConfig
    run: RunConfig
    mesh: Mesh
    n_slots: int
    max_len: int
    prefill_step: Callable   # (params, pstate, tokens[1,c], offset) ->
    #                          (pstate, last_logits [1,V] f32)
    insert_step: Callable    # (state, pstate, slot) -> state
    decode_step: Callable    # (params, state, tok[B,1], pos[B], active[B],
    #                          rids[B], ngen[B], temp[B], topk[B], topp[B])
    #                          -> (state, next[B], last_logits [B,V] f32)
    sample_step: Callable    # (logits[N,V], rids, ngen, temp, topk, topp)
    init_state: Callable     # () -> batched decode state (B = n_slots)
    init_pstate: Callable    # () -> batch-1 prefill decode state
    param_shardings: object
    state_shardings: object
    paged: bool = False
    page_size: int = 0
    n_pages: int = 0
    max_pages: int = 0       # page-table slots per request
    init_prec: Callable = None  # () -> batch-1 prefill recurrent carry
    fork_step: Callable = None  # (state, src[1], dst[1]) -> state (COW §14)
    # EP decode (DESIGN.md §11): when set, expert weights are sharded over
    # ep.ep_axis, params must be placed (serve/ep_decode.place_params) and
    # decode_step returns a 4th output — the per-layer routed-copy
    # histogram [n_rows, n_experts] feeding the placement EMA.
    ep: object = None


def paged_state_specs(cfg: ModelConfig, mesh: Mesh, rules: ShardingRules,
                      batch: int, n_pages: int, page_size: int,
                      dtype=jnp.bfloat16):
    """PartitionSpecs for the PAGED decode-state tree (DESIGN.md §9).

    KV pools shard their page dim over "model" (`paged_pool_spec` — the
    paged analogue of the dense cache sharding its sequence dim there);
    per-slot recurrent states keep the dense layout (batch over "data",
    channels over "model") via the shared `_state_spec_for` mapper."""
    from repro.sharding.rules import paged_pool_spec
    baxes = fit_batch_axes(batch, mesh, rules.batch_axes)
    b = baxes if baxes else None
    spec_for = _state_spec_for(
        cfg, mesh, b,
        lambda tail, nd: paged_pool_spec(n_pages, mesh, rules, ndim=nd))

    state_shapes = jax.eval_shape(
        lambda: stack.init_paged_decode_state(cfg, batch, n_pages,
                                              page_size, dtype))
    from repro.pytree import tree_map_with_path_names
    return state_shapes, tree_map_with_path_names(spec_for, state_shapes)


def make_continuous_program(cfg: ModelConfig, mesh: Mesh, run: RunConfig, *,
                            serve_cfg=None,
                            n_slots: int | None = None,
                            max_len: int | None = None, seed: int = 0,
                            page_size: int | None = None,
                            n_pages: int | None = None,
                            ep=None) -> ContinuousProgram:
    """Build the jit'd steps of the continuous-batching engine.

    ``serve_cfg`` (a :class:`repro.serve.config.ServeConfig`) is the
    preferred input — slots, max_len, seed and the paged geometry all come
    from it; the bare ``n_slots``/``max_len``/``page_size``/``n_pages``
    kwargs remain as the legacy spelling for existing call sites.

    ``page_size`` switches on the paged-KV build (DESIGN.md §9): KV moves
    into shared ``[n_pages, page_size, ...]`` pools addressed through
    per-slot page tables, prefill writes its allocated pages directly into
    the pool, and admission copies only the recurrent carry. ``n_pages``
    defaults to full reservation capacity (n_slots x pages-per-sequence);
    benchmarks pass smaller pools to measure paging's slot lift at fixed
    HBM (bench_serve.py --paged).

    Decode carries a per-slot position vector ``pos [B]`` (the next cache
    line of each slot; -1 for dead slots, whose cache writes are dropped
    and whose query positions mask out every key) instead of the lockstep
    scalar ``cache_index``. Prefill runs at batch 1 — chunked, attending
    over its own cache — and the finished cache is inserted into a free
    slot by a batch-axis ``dynamic_update_slice`` over every decode-state
    leaf, so live slots are never touched.

    MoE FFNs take the dropless gather path (``apply_moe`` -> single-pack
    ``ops.moe_ffn``): no capacity, so dead-slot tokens can never displace
    live tokens, and decode shapes auto-route to the group-dense small-M
    fallback (DESIGN.md §5.5). With ``ep`` (an
    ``serve.ep_decode.EPDecodeConfig``) expert weights are instead sharded
    over the EP axis and the MoE hop runs the chunked all-to-all dispatch
    (DESIGN.md §11); ``decode_step`` then returns a 4th output, the
    per-layer routed-copy histogram.
    """
    if serve_cfg is not None:
        n_slots = serve_cfg.slots
        max_len = serve_cfg.max_len
        seed = serve_cfg.seed
        if serve_cfg.paged.enabled:
            page_size = serve_cfg.paged.page_size
            n_pages = serve_cfg.paged.pool_pages
    assert n_slots is not None and max_len is not None, \
        "pass serve_cfg or the legacy n_slots/max_len kwargs"
    assert not cfg.is_encdec and cfg.vision_seq == 0, \
        "continuous batching supports decoder-only LMs"
    if page_size is not None:
        return _make_paged_program(cfg, mesh, run, n_slots=n_slots,
                                   max_len=max_len, seed=seed,
                                   page_size=page_size, n_pages=n_pages,
                                   ep=ep)
    rules = rules_for(cfg, mesh, variant="serve")
    B = n_slots
    from repro.sharding.rules import fitted_shardings, make_constrainer
    pshapes, paxes = abstract_params(cfg)
    psh = fitted_shardings(pshapes, paxes, rules, mesh)
    dtype = run.policy.compute_dtype

    ep_moe = None
    if ep is not None:
        from repro.serve import ep_decode as epd
        epd.validate_ep_config(cfg, mesh, ep)
        psh = epd.ep_param_shardings(psh, pshapes, mesh, ep)
        ep_moe = epd.make_ep_moe_decode(mesh, cfg, run, ep)
        ep_extras = (("ep_counts", (cfg.n_experts,)),)
        ep_prefill_ov = epd.moe_override_for(ep_moe)
        ep_decode_ov = epd.moe_override_for

    _, sspecs = decode_state_specs(cfg, mesh, rules, B, max_len, dtype)
    ssh = jax.tree.map(lambda s: NamedSharding(mesh, s), sspecs,
                       is_leaf=lambda x: isinstance(x, P))
    _, pspecs = decode_state_specs(cfg, mesh, rules, 1, max_len, dtype)
    pssh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                        is_leaf=lambda x: isinstance(x, P))

    baxes = fit_batch_axes(B, mesh, rules.batch_axes)
    run_b = dataclasses.replace(run, constrain=make_constrainer(
        dataclasses.replace(rules, batch_axes=baxes), mesh))
    run_p = dataclasses.replace(run, constrain=make_constrainer(
        dataclasses.replace(rules, batch_axes=()), mesh))
    vec_sh = NamedSharding(mesh, slot_vector_spec(B, mesh, rules))
    tok_sh = NamedSharding(mesh, P(baxes if baxes else None, None))
    base_key = jax.random.PRNGKey(seed)

    from repro.models import modules

    def prefill(params, pstate, tokens, offset):
        """One prompt chunk at batch 1: writes cache lines
        [offset, offset+c), attends over the whole cache (earlier chunks
        included), returns f32 logits of the chunk's last position."""
        hidden, pstate, _ = stack.apply_model(
            params, cfg, run_p, tokens, decode_state=pstate,
            cache_index=offset, attend_to_cache=True, return_hidden=True,
            moe_override=ep_prefill_ov if ep_moe is not None else None)
        last = modules.apply_unembedding(
            params["embed"], params.get("lm_head"), cfg, run.policy,
            hidden[:, -1])
        return pstate, last.astype(jnp.float32)

    def insert(state, pstate, slot):
        """Overwrite slot ``slot`` of every decode-state leaf with the
        batch-1 prefilled state (batch axis: 1 for scan-stacked block
        leaves, 0 for tail leaves). A full overwrite — KV, cache
        positions, recurrent states — so recycled slots cannot leak."""
        def ins(axis):
            return lambda d, s: jax.lax.dynamic_update_slice_in_dim(
                d, s.astype(d.dtype), slot, axis=axis)
        new = {"blocks": None, "tails":
               jax.tree.map(ins(0), state["tails"], pstate["tails"])}
        if state["blocks"] is not None:
            new["blocks"] = jax.tree.map(ins(1), state["blocks"],
                                         pstate["blocks"])
        return new

    def decode(params, state, tok, pos, active, rids, ngen, temp, topk,
               topp):
        """One decode step for every slot; dead slots (pos < 0) write no
        cache lines and emit token 0. Under EP the per-layer routed-copy
        histogram rides along as a 4th output."""
        if ep_moe is not None:
            logits, state, aux = stack.apply_model(
                params, cfg, run_b, tok, decode_state=state,
                cache_index=pos, moe_override=ep_decode_ov(ep_moe, active),
                aux_extras=ep_extras, layer_aux=True)
        else:
            logits, state, _ = stack.apply_model(
                params, cfg, run_b, tok, decode_state=state,
                cache_index=pos)
        last = logits[:, -1].astype(jnp.float32)
        keys = sampling.request_keys(base_key, rids, ngen)
        nxt = sampling.sample_tokens(last, keys, temp, topk, topp)
        if ep_moe is not None:
            return (state, jnp.where(active, nxt, 0), last,
                    aux["per_layer"]["ep_counts"])
        return state, jnp.where(active, nxt, 0), last

    def sample(logits, rids, ngen, temp, topk, topp):
        keys = sampling.request_keys(base_key, rids, ngen)
        return sampling.sample_tokens(logits.astype(jnp.float32), keys,
                                      temp, topk, topp)

    jit_prefill = jax.jit(prefill, in_shardings=(psh, pssh, None, None),
                          out_shardings=(pssh, None), donate_argnums=(1,))
    jit_insert = jax.jit(insert, in_shardings=(ssh, pssh, None),
                         out_shardings=ssh, donate_argnums=(0,))
    dec_out = (ssh, None, None) if ep_moe is None else (ssh, None, None,
                                                        None)
    jit_decode = jax.jit(
        decode,
        in_shardings=(psh, ssh, tok_sh) + (vec_sh,) * 7,
        out_shardings=dec_out, donate_argnums=(1,))

    return ContinuousProgram(
        cfg=cfg, run=run, mesh=mesh, n_slots=B, max_len=max_len,
        prefill_step=jit_prefill, insert_step=jit_insert,
        decode_step=jit_decode, sample_step=jax.jit(sample),
        init_state=jax.jit(
            lambda: stack.init_decode_state(cfg, B, max_len, dtype),
            out_shardings=ssh),
        init_pstate=jax.jit(
            lambda: stack.init_decode_state(cfg, 1, max_len, dtype),
            out_shardings=pssh),
        param_shardings=psh, state_shardings=ssh, ep=ep)


def _make_paged_program(cfg: ModelConfig, mesh: Mesh, run: RunConfig, *,
                        n_slots: int, max_len: int, seed: int,
                        page_size: int, n_pages: int | None,
                        ep=None) -> ContinuousProgram:
    """Paged-KV build of the continuous program (DESIGN.md §9.4).

    KV never moves at admission or recycling: prefill scatters straight
    into the request's allocated pool pages (disjoint from every live
    slot's), the insert step copies only the batch-1 recurrent carry into
    the slot row, and freeing is the allocator's page-table reset. Decode
    carries ``pos [B]`` plus page tables ``[B, max_pages]``.
    """
    rules = rules_for(cfg, mesh, variant="serve")
    B = n_slots
    from repro.sharding.rules import (fitted_shardings, make_constrainer,
                                      page_table_spec)
    pshapes, paxes = abstract_params(cfg)
    psh = fitted_shardings(pshapes, paxes, rules, mesh)
    dtype = run.policy.compute_dtype

    ep_moe = None
    if ep is not None:
        from repro.serve import ep_decode as epd
        epd.validate_ep_config(cfg, mesh, ep)
        psh = epd.ep_param_shardings(psh, pshapes, mesh, ep)
        ep_moe = epd.make_ep_moe_decode(mesh, cfg, run, ep)
        ep_extras = (("ep_counts", (cfg.n_experts,)),)
        ep_prefill_ov = epd.moe_override_for(ep_moe)
        ep_decode_ov = epd.moe_override_for

    max_pages = -(-max_len // page_size)
    n_pages = n_pages if n_pages is not None else B * max_pages
    assert n_pages >= max_pages, "pool smaller than one sequence"

    _, sspecs = paged_state_specs(cfg, mesh, rules, B, n_pages, page_size,
                                  dtype)
    ssh = jax.tree.map(lambda s: NamedSharding(mesh, s), sspecs,
                       is_leaf=lambda x: isinstance(x, P))
    # Prefill recurrent carry: the non-KV part of a batch-1 dense state
    # (recurrent shapes are max_len-independent).
    _, pspecs = decode_state_specs(cfg, mesh, rules, 1, 1, dtype)
    prec_specs = stack.split_kv_state(pspecs)[1]
    prec_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), prec_specs,
                           is_leaf=lambda x: isinstance(x, P))

    baxes = fit_batch_axes(B, mesh, rules.batch_axes)
    run_b = dataclasses.replace(run, constrain=make_constrainer(
        dataclasses.replace(rules, batch_axes=baxes), mesh))
    run_p = dataclasses.replace(run, constrain=make_constrainer(
        dataclasses.replace(rules, batch_axes=()), mesh))
    vec_sh = NamedSharding(mesh, slot_vector_spec(B, mesh, rules))
    ptab_sh = NamedSharding(mesh, page_table_spec(B, mesh, rules))
    tok_sh = NamedSharding(mesh, P(baxes if baxes else None, None))
    base_key = jax.random.PRNGKey(seed)

    from repro.models import modules

    def prefill(params, state, prec, tokens, offset, ptrow):
        """One prompt chunk at batch 1, scattered through the request's
        page table straight into the shared pools; recurrent layers carry
        their batch-1 state in ``prec``."""
        kv_s, rec_s = stack.split_kv_state(state)
        merged = stack.merge_kv_state(kv_s, prec)
        hidden, new_merged, _ = stack.apply_model(
            params, cfg, run_p, tokens, decode_state=merged,
            cache_index=offset, attend_to_cache=True, return_hidden=True,
            page_table=ptrow,
            moe_override=ep_prefill_ov if ep_moe is not None else None)
        kv_n, prec_n = stack.split_kv_state(new_merged)
        last = modules.apply_unembedding(
            params["embed"], params.get("lm_head"), cfg, run.policy,
            hidden[:, -1])
        return (stack.merge_kv_state(kv_n, rec_s), prec_n,
                last.astype(jnp.float32))

    def insert(state, prec, slot):
        """Admission copies ONLY the recurrent carry into the slot row —
        the KV pages are already in the pool (written by prefill) and are
        exposed by the host updating the slot's page-table row."""
        kv_s, rec_s = stack.split_kv_state(state)

        def ins(axis):
            return lambda d, s: jax.lax.dynamic_update_slice_in_dim(
                d, s.astype(d.dtype), slot, axis=axis)
        new_rec = {"blocks": None, "tails":
                   jax.tree.map(ins(0), rec_s["tails"], prec["tails"])}
        if rec_s["blocks"] is not None:
            new_rec["blocks"] = jax.tree.map(ins(1), rec_s["blocks"],
                                             prec["blocks"])
        return stack.merge_kv_state(kv_s, new_rec)

    def decode(params, state, tok, pos, ptabs, active, rids, ngen, temp,
               topk, topp):
        if ep_moe is not None:
            logits, state, aux = stack.apply_model(
                params, cfg, run_b, tok, decode_state=state,
                cache_index=pos, page_table=ptabs,
                moe_override=ep_decode_ov(ep_moe, active),
                aux_extras=ep_extras, layer_aux=True)
        else:
            logits, state, _ = stack.apply_model(
                params, cfg, run_b, tok, decode_state=state,
                cache_index=pos, page_table=ptabs)
        last = logits[:, -1].astype(jnp.float32)
        keys = sampling.request_keys(base_key, rids, ngen)
        nxt = sampling.sample_tokens(last, keys, temp, topk, topp)
        if ep_moe is not None:
            return (state, jnp.where(active, nxt, 0), last,
                    aux["per_layer"]["ep_counts"])
        return state, jnp.where(active, nxt, 0), last

    def sample(logits, rids, ngen, temp, topk, topp):
        keys = sampling.request_keys(base_key, rids, ngen)
        return sampling.sample_tokens(logits.astype(jnp.float32), keys,
                                      temp, topk, topp)

    def fork(state, src, dst):
        """Copy-on-write page copy (DESIGN.md §14): duplicate physical
        page ``src`` into ``dst`` across every layer's K/V pool before a
        writer diverges from a shared prefix. One page of device traffic —
        the only KV copy anywhere in the paged engine."""
        return stack.scatter_kv_pages(
            state, stack.gather_kv_pages(state, src), dst)

    jit_fork = jax.jit(fork, in_shardings=(ssh, None, None),
                       out_shardings=ssh, donate_argnums=(0,))

    jit_prefill = jax.jit(prefill,
                          in_shardings=(psh, ssh, prec_sh, None, None, None),
                          out_shardings=(ssh, prec_sh, None),
                          donate_argnums=(1, 2))
    jit_insert = jax.jit(insert, in_shardings=(ssh, prec_sh, None),
                         out_shardings=ssh, donate_argnums=(0,))
    dec_out = (ssh, None, None) if ep_moe is None else (ssh, None, None,
                                                        None)
    jit_decode = jax.jit(
        decode,
        in_shardings=(psh, ssh, tok_sh, vec_sh, ptab_sh) + (vec_sh,) * 6,
        out_shardings=dec_out, donate_argnums=(1,))

    return ContinuousProgram(
        cfg=cfg, run=run, mesh=mesh, n_slots=B, max_len=max_len,
        prefill_step=jit_prefill, insert_step=jit_insert,
        decode_step=jit_decode, sample_step=jax.jit(sample),
        init_state=jax.jit(
            lambda: stack.init_paged_decode_state(cfg, B, n_pages,
                                                  page_size, dtype),
            out_shardings=ssh),
        init_pstate=None,
        param_shardings=psh, state_shardings=ssh,
        paged=True, page_size=page_size, n_pages=n_pages,
        max_pages=max_pages, ep=ep, fork_step=jit_fork,
        init_prec=jax.jit(
            lambda: stack.split_kv_state(
                stack.init_decode_state(cfg, 1, 1, dtype))[1],
            out_shardings=prec_sh))


class ContinuousBatchingEngine:
    """Continuous-batching serving loop (DESIGN.md §7).

    One ``tick`` = up to ``scheduler.token_budget`` chunked-prefill tokens
    (admitting at most one request at a time into a freed slot) followed
    by ONE batched decode step over all live slots. Requests finish and
    free their slot on EOS or length limit while other slots keep
    decoding; generated tokens land in ``results[rid]``.

    With a paged program (DESIGN.md §9.4) the scheduler must carry a
    ``BlockAllocator``; the engine mirrors each slot's page table, claims
    a page whenever a slot's next write position crosses a page boundary,
    and relieves pool OOM by preempting the newest running request
    (``scheduler.preempt_newest``) before the decode step runs.
    """

    def __init__(self, program: ContinuousProgram, params,
                 scheduler: Scheduler, *, metrics: ServeMetrics = None,
                 on_token: Callable = None, record_logits: bool = False):
        self.p = program
        self.params = compute_params(program, params)
        self.sched = scheduler
        self.metrics = metrics or ServeMetrics()
        self.on_token = on_token  # callable(rid, token, finished)
        self.record_logits = record_logits
        self.logits: Dict[int, List[np.ndarray]] = {}  # rid -> [V] rows
        self.rejected: List[int] = []  # rids refused admission
        self.tick_count = 0
        self.track = "serve"  # tracer track (fleet/disagg override per role)
        self.owns_clock = True  # standalone: this engine advances the tracer
        scheduler.set_track(self.track)
        B = program.n_slots
        with program.mesh:
            self.state = program.init_state()
        self.pstate = None
        self.prec = None  # paged mode: batch-1 prefill recurrent carry
        # Host mirrors of the per-slot decode inputs.
        self._tok = np.zeros((B,), np.int32)
        self._pos = np.full((B,), -1, np.int32)
        self._active = np.zeros((B,), bool)
        self._rid = np.zeros((B,), np.int32)
        self._ngen = np.zeros((B,), np.int32)
        self._temp = np.zeros((B,), np.float32)
        self._topk = np.zeros((B,), np.int32)
        self._topp = np.ones((B,), np.float32)
        if program.paged:
            alloc = scheduler.allocator
            assert alloc is not None, "paged program needs an allocator"
            assert alloc.page_size == program.page_size \
                and alloc.n_pages == program.n_pages \
                and alloc.max_pages_per_seq >= program.max_pages, \
                "allocator geometry disagrees with the program"
            self._ptab = np.full((B, program.max_pages), -1, np.int32)
            # page-pool occupancy stats (simulated-HBM benchmark inputs)
            self.page_peak = 0
            self._page_ticks: List[tuple] = []  # (pages_in_use, n_active)

    @property
    def results(self) -> Dict[int, List[int]]:
        return self.sched.results

    def set_track(self, track: str) -> None:
        """Point this engine's trace events at ``track`` (fleet groups use
        g{gid}, disagg roles use prefill/decode). Controllers that call
        this own the tick clock, so the engine stops advancing it."""
        self.track = track
        self.owns_clock = False
        self.sched.set_track(track)

    def submit(self, req: Request) -> None:
        self.sched.submit(req)
        self.metrics.on_submit(req.rid, len(req.prompt))
        obs_trace.TRACER.flow(self.track, "queued", req.rid,
                              prompt=len(req.prompt))

    # -- one engine tick ----------------------------------------------------

    def tick(self) -> None:
        """One tick, on the profiler's clock (``obs.trace``) the span
        ``repro.tick``: ``schedule`` (prefill planning, page bookkeeping),
        ``prefill`` (one chunk's dispatch), ``admit`` (sample, insert),
        ``decode`` (the decode dispatch), ``sync`` (each blocking read of a
        device result) and ``emit`` (the per-slot token loop) inside it."""
        with obs_trace.host_span("tick"):
            tr = obs_trace.TRACER
            if self.owns_clock:
                tr.advance(self.tick_count)
            worked = False
            budget = self.sched.token_budget
            while budget > 0:
                with obs_trace.host_span("schedule"):
                    chunk = self.sched.plan_prefill(budget)
                if chunk is None:
                    break
                with tr.span(self.track, "prefill", rid=chunk.request.rid,
                             start=chunk.start, length=chunk.length):
                    if chunk.first:
                        tr.flow(self.track, "prefill", chunk.request.rid)
                    self._run_prefill_chunk(chunk)
                worked = True
                budget -= chunk.length
            if self.p.paged:
                with obs_trace.host_span("schedule"):
                    self._ensure_pages()
            if self._active.any():
                with tr.span(self.track, "decode",
                             n_active=int(self._active.sum())):
                    self._decode_once()
                worked = True
            if tr.enabled:
                tr.count(self.track, "queue_depth", self.sched.queue_depth)
                if not worked:
                    bucket = "pool-OOM" \
                        if self.sched.prefill.wait_reason == "pages" \
                        else "queue-starved"
                    tr.mark_idle(self.track, bucket)
            self.metrics.on_tick(self.sched.queue_depth, self.sched.n_active)
            if self.p.paged:
                in_use = self.sched.allocator.pages_in_use
                self.page_peak = max(self.page_peak, in_use)
                self._page_ticks.append((in_use, self.sched.n_active))
            self.tick_count += 1

    def _run_prefill_chunk(self, chunk: PrefillChunk) -> None:
        req = chunk.request
        toks = np.asarray(
            chunk.tokens[chunk.start:chunk.start + chunk.length],
            np.int32)[None, :]
        if self.p.paged:
            if chunk.first:  # fresh (or resumed) -> fresh rec carry;
                # a prefix hit starts at chunk.skipped, not 0 (§14)
                with self.p.mesh:
                    self.prec = self.p.init_prec()
            # Fork-on-divergence: this chunk writes lines
            # [start, start+length) — any SHARED page in that range must
            # be COW-forked before the scatter lands (a resumed mid-page
            # prefill into a cached partial tail is the canonical case).
            with obs_trace.host_span("schedule"):
                self._cow_guard(req.rid, chunk.start, chunk.length)
                ptrow = jnp.asarray(self.sched.allocator.table(
                    req.rid, self.p.max_pages))[None, :]
            with self.p.mesh:
                self.state, self.prec, logits = self.p.prefill_step(
                    self.params, self.state, self.prec, toks,
                    jnp.asarray(chunk.start, jnp.int32), ptrow)
        else:
            if chunk.start == 0:  # fresh request -> fresh prefill cache
                with self.p.mesh:
                    self.pstate = self.p.init_pstate()
            with self.p.mesh:
                self.pstate, logits = self.p.prefill_step(
                    self.params, self.pstate, toks,
                    jnp.asarray(chunk.start, jnp.int32))
        if self.sched.finish_prefill_chunk(chunk):
            self._admit(chunk, logits)

    def _admit(self, chunk: PrefillChunk, last_logits) -> None:
        """Sample the next token from the prefill logits and insert the
        prefilled state into the freed slot. For a preemption resume
        (``chunk.n_done > 0``) the re-prefill replayed prompt + generated
        tokens, so the sample index continues at ``n_done`` — key(rid, n)
        makes the continuation token-exact (§7.4)."""
        req, slot = chunk.request, chunk.slot
        sp = req.sampling
        with obs_trace.host_span("admit"), self.p.mesh:
            first = self.p.sample_step(
                last_logits, np.asarray([req.rid], np.int32),
                np.asarray([chunk.n_done], np.int32),
                np.asarray([sp.temperature], np.float32),
                np.asarray([sp.top_k], np.int32),
                np.asarray([sp.top_p], np.float32))
            if self.p.paged:
                self.state = self.p.insert_step(self.state, self.prec,
                                                jnp.asarray(slot, jnp.int32))
                self.prec = None
                self._ptab[slot] = self.sched.allocator.table(
                    req.rid, self.p.max_pages)
            else:
                self.state = self.p.insert_step(self.state, self.pstate,
                                                jnp.asarray(slot, jnp.int32))
                self.pstate = None
            with obs_trace.host_span("sync"):
                first = int(np.asarray(first)[0])
                if self.record_logits:
                    row = np.asarray(last_logits)[0]
        if self.record_logits:
            if chunk.n_done == 0:
                self.logits[req.rid] = [row]
            else:
                self.logits[req.rid].append(row)
        self.metrics.on_token(req.rid, self.tick_count)
        finished = self.sched.activate(chunk, first)
        if self.on_token:
            self.on_token(req.rid, first, finished)
        if finished:
            self.metrics.on_finish(req.rid, self.tick_count)
            if self.p.paged:
                self._ptab[slot] = -1
            return
        self._tok[slot] = first
        self._pos[slot] = len(chunk.tokens)
        self._active[slot] = True
        self._rid[slot] = req.rid
        self._ngen[slot] = chunk.n_done + 1
        self._temp[slot] = sp.temperature
        self._topk[slot] = sp.top_k
        self._topp[slot] = sp.top_p

    def _cow_guard(self, rid: int, line_start: int, n_lines: int,
                   slot: Optional[int] = None) -> None:
        """COW-fork every SHARED page of ``rid`` that the upcoming write
        to lines [line_start, line_start + n_lines) would touch
        (DESIGN.md §14): a fresh page replaces the shared one in the
        table and ``fork_step`` copies its device lines, so no writer
        ever mutates a page with refcount > 1. On pool exhaustion the
        newest running request is preempted for the copy target."""
        alloc = self.sched.allocator
        ps = alloc.page_size
        table = alloc.tables.get(rid)
        if not table or n_lines <= 0:
            return
        lo = line_start // ps
        hi = min((line_start + n_lines - 1) // ps, len(table) - 1)
        for pslot in range(lo, hi + 1):
            if not alloc.is_shared(table[pslot]):
                continue
            while True:
                try:
                    old, new = alloc.cow_fork(rid, pslot)
                    break
                except MemoryError:
                    victim = self.sched.preempt_newest()
                    assert victim is not None, \
                        "COW OOM with nothing to preempt"
                    self._clear_slot(victim)
                    if slot is not None and victim == slot:
                        return  # the writer itself was evicted; it resumes
            with self.p.mesh:
                self.state = self.p.fork_step(
                    self.state, jnp.asarray([old], jnp.int32),
                    jnp.asarray([new], jnp.int32))
            if slot is not None:
                self._ptab[slot] = alloc.table(rid, self.p.max_pages)

    def _ensure_pages(self) -> None:
        """Claim a pool page for every live slot whose next write position
        has crossed its allocated frontier; on pool OOM, preempt the newest
        running request (oldest slots are served first so eviction order is
        newest-first and the loop always converges — down to one live
        request, which submit() guaranteed fits the pool). With a prefix
        cache, a slot about to write into a still-shared page COW-forks it
        first (the decode half of fork-on-divergence, §14)."""
        alloc = self.sched.allocator
        order = sorted((int(s) for s in np.nonzero(self._active)[0]),
                       key=lambda s: self.sched.running[s].seq)
        for slot in order:
            if not self._active[slot]:
                continue  # evicted by an earlier slot's OOM relief
            rid = int(self._rid[slot])
            while not alloc.covers(rid, int(self._pos[slot])):
                if alloc.extend(rid):
                    self._ptab[slot] = alloc.table(rid, self.p.max_pages)
                    continue
                victim = self.sched.preempt_newest()
                assert victim is not None, "OOM with nothing to preempt"
                self._clear_slot(victim)
                if victim == slot:
                    break  # this slot itself was evicted; it will resume
            if self._active[slot]:
                self._cow_guard(rid, int(self._pos[slot]), 1, slot=slot)

    def _decode_once(self) -> None:
        with self.p.mesh:
            if self.p.paged:
                out = self.p.decode_step(
                    self.params, self.state, self._tok[:, None], self._pos,
                    self._ptab, self._active, self._rid, self._ngen,
                    self._temp, self._topk, self._topp)
            else:
                out = self.p.decode_step(
                    self.params, self.state, self._tok[:, None], self._pos,
                    self._active, self._rid, self._ngen, self._temp,
                    self._topk, self._topp)
        if self.p.ep is not None:
            self.state, nxt, logits, counts = out
            self._on_ep_counts(counts)
        else:
            self.state, nxt, logits = out
        with obs_trace.host_span("sync"):
            nxt = np.asarray(nxt)
            if self.record_logits:
                logits = np.asarray(logits)
        with obs_trace.host_span("emit"):
            for slot in np.nonzero(self._active)[0]:
                slot = int(slot)
                tok = int(nxt[slot])
                rid = int(self._rid[slot])
                if self.record_logits:
                    self.logits[rid].append(logits[slot])
                self.metrics.on_token(rid, self.tick_count)
                finished = self.sched.note_token(slot, tok)
                if self.on_token:
                    self.on_token(rid, tok, finished)
                if finished:
                    self.metrics.on_finish(rid, self.tick_count)
                    self._release(slot)
                else:
                    self._tok[slot] = tok
                    self._pos[slot] += 1
                    self._ngen[slot] += 1

    def _on_ep_counts(self, counts) -> None:
        """Routing-histogram hook (EP decode): overridden by
        serve.ep_decode.EPContinuousBatchingEngine to feed the placement
        EMA; a plain engine driving an EP program just drops the counts."""

    def _release(self, slot: int) -> None:
        self._clear_slot(slot)

    def _clear_slot(self, slot: int) -> None:
        self._active[slot] = False
        self._pos[slot] = -1
        self._tok[slot] = 0
        self._ngen[slot] = 0
        self._temp[slot] = 0.0
        self._topk[slot] = 0
        self._topp[slot] = 1.0
        if self.p.paged:
            self._ptab[slot] = -1

    def page_occupancy(self) -> dict:
        """Simulated-HBM occupancy stats over the run (paged mode): peak
        pages in use and the time-averaged cache lines held per active
        slot — the quantities bench_serve.py --paged turns into the
        slots-at-fixed-HBM comparison against the reservation engine."""
        assert self.p.paged
        ticks = [t for t in self._page_ticks if t[1] > 0]
        lines = [p * self.p.page_size / a for p, a in ticks]
        alloc = self.sched.allocator
        return {
            "page_size": self.p.page_size,
            "n_pages": self.p.n_pages,
            "page_peak": self.page_peak,
            "mean_lines_per_active_slot":
                round(sum(lines) / len(lines), 2) if lines else 0.0,
            "n_preempted": self.sched.n_preempted,
            # prefix-cache accounting (§14; zeros when caching is off)
            "pages_allocated": alloc.n_fresh_allocs,
            "pages_shared": alloc.n_shared_allocs,
            "n_cow_forks": alloc.n_cow_forks,
            "prefix_hits": self.sched.prefill.n_prefix_hits,
            "tokens_skipped": self.sched.prefill.n_tokens_skipped,
        }

    # -- trace driver -------------------------------------------------------

    def run(self, requests: List[Request], max_ticks: int = 100_000):
        """Drive a trace to completion. ``Request.arrival`` is in engine
        ticks (the simulated clock); requests are submitted when the tick
        counter reaches their arrival time."""
        pending = sorted(requests, key=lambda r: r.arrival)
        while True:
            while pending and pending[0].arrival <= self.tick_count:
                req = pending.pop(0)
                try:
                    self.submit(req)
                except ValueError:
                    # inadmissible (oversized / empty) — reject this
                    # request, keep serving the rest
                    self.rejected.append(req.rid)
            if not pending and not self.sched.has_work() \
                    and not self._active.any():
                return self.results
            self.tick()
            if self.tick_count > max_ticks:
                raise RuntimeError(f"serve trace exceeded {max_ticks} ticks")
