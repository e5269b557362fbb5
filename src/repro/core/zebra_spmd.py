"""Zebra parallelism — single-program (SPMD) engine.

The paper's ZP overlaps (a) attention compute of microbatch k with expert
compute of microbatch k-1 and (b) compute with dispatch/combine all-to-alls,
using CUDA streams. The TPU/XLA adaptation: the MoE layer is executed as a
``lax.scan`` software pipeline whose step k computes

    attention(mb k)     ||     dispatch+experts+combine(mb k-1)

with no data dependence between the two halves — XLA's async scheduler then
overlaps them and the collectives, which is the TPU-native equivalent of
multi-stream scheduling (DESIGN.md §2). Autodiff of the scan reverses the
pipeline, reproducing the paper's backward zigzag for free.

Two expert-parallel dispatch modes (ZebraConfig.mode):

  * "alltoall"   — paper-faithful EP: token batch sharded over the expert
    ("model") axis too; tokens are capacity-packed per expert and exchanged
    with ``lax.all_to_all`` (dispatch), computed on their expert shard, and
    exchanged back (combine). Microbatching requires global_batch >=
    R * n_batch_shards. With ``n_chunks > 1`` the dispatch buffer streams
    in capacity chunks double-buffered against the expert GEMMs, and with
    ``offload_experts > 0`` the leading experts stay replicated
    attention-side, folded into the first chunk's unified grouped GEMM
    (DESIGN.md §8).
  * "replicated" — TPU-native hybrid (TP attention + EP experts): batch is
    sharded over "data" only, so activations are replicated across the
    expert axis; each expert shard *selects* its own tokens locally (the
    dispatch all-to-all becomes free) and partial outputs are combined with
    a psum. Enables zebra pipelining at full-pod scale where the per-chip
    batch is 1 sequence.

Both modes are numerically equivalent to models/modules.apply_moe up to
capacity drops (tests use capacity_factor >= n_experts/top_k for equality).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.models import modules
from repro.models.config import LayerSpec, ModelConfig
from repro.models.modules import RunConfig
from repro.obs import trace as obs_trace


@dataclasses.dataclass(frozen=True)
class ZebraConfig:
    num_microbatches: int = 4
    mode: str = "replicated"  # replicated | alltoall
    ep_axis: str = "model"
    batch_axes: tuple = ("data",)  # axes the token batch is sharded over
    capacity_factor: float = 1.25
    pipeline: bool = True  # False -> sequential EP (paper's "EP"/DistEP)
    # Chunked dispatch (alltoall mode): the [E, C, d] dispatch buffer is
    # split into n_chunks capacity slices; the all-to-all of chunk k+1 has
    # no data dependence on the expert GEMM of chunk k, so XLA's async
    # scheduler double-buffers communication under compute (DESIGN.md §8).
    n_chunks: int = 1
    # Combine-side chunk count (alltoall mode), decoupled from dispatch:
    # combine cotangents are f32 in the backward — 2x the wire volume of
    # the bf16 dispatch at equal chunk count — so the reverse all-to-all
    # needs finer slicing to hide under the same expert compute. None
    # defaults to 2x the dispatch chunks (1 when dispatch is serialized);
    # must be a multiple of n_chunks so every dispatch chunk's output
    # splits into whole combine sub-chunks.
    n_chunks_combine: Optional[int] = None
    # Asym-EA-style offload (alltoall mode): experts [0, offload_experts)
    # live replicated on every shard ("attention-side"); their tokens skip
    # the all-to-all entirely and their GEMM is folded into the FIRST
    # chunk's unified grouped call (ops.moe_ffn_packed_multi), filling the
    # bubble while chunk 0 of the remote dispatch is in flight.
    offload_experts: int = 0


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# ---------------------------------------------------------------------------
# Local capacity packing (shared by both modes)
# ---------------------------------------------------------------------------

def _pack(x, idx, E: int, C: int):
    """Pack tokens into fixed [E, C, d] buffers by routed expert.

    x: [T, d]; idx: [T, k]. Returns (buf [E,C,d], meta). Tokens beyond
    capacity are dropped (residual passthrough, standard GShard semantics).

    All d-wide data movement is GATHERS driven by cheap int32 index maps
    (scatters of [*, d] values are slow on TPU and are charged ~2x the
    traffic in the HLO byte model).
    """
    T, d = x.shape
    k = idx.shape[1]
    flat = idx.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sorted_e = jnp.take(flat, order)
    counts = jnp.bincount(flat, length=E)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(T * k, dtype=jnp.int32) - starts[sorted_e]
    keep = pos_in_e < C
    slot = sorted_e * C + jnp.where(keep, pos_in_e, 0)
    tok = order // k
    # slot -> source-row map (cheap int32 scatter; dropped entries write to
    # a trash slot so they can never shadow a kept slot). Row T of the
    # padded source is the zero row.
    slot_or_trash = jnp.where(keep, slot, E * C)
    idx_map = jnp.full((E * C + 1,), T, jnp.int32).at[slot_or_trash].set(
        tok.astype(jnp.int32))[:E * C]
    x_pad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)], axis=0)
    buf = jnp.take(x_pad, idx_map, axis=0)  # [E*C, d] gather
    return buf.reshape(E, C, d), (tok, slot, keep, order)


def _unpack(buf, meta, weights, T: int):
    """Weighted combine back to [T, d] — inverse-permutation gather +
    reshape-sum over the k copies (no d-wide scatter)."""
    tok, slot, keep, order = meta
    d = buf.shape[-1]
    k = order.shape[0] // T
    vals = jnp.take(buf.reshape(-1, d), slot, axis=0)  # [T*k, d] sorted
    w = jnp.take(weights.reshape(-1), order)
    vals = vals * jnp.where(keep, w, 0.0).astype(vals.dtype)[:, None]
    inv = jnp.argsort(order)  # inverse permutation -> token-major order
    return jnp.take(vals, inv, axis=0).reshape(T, k, d).sum(axis=1)


def _experts_dense(wi_gate, wi_up, wo, buf, cd, use_kernel: bool = False):
    """Per-expert FFN over packed buffers. buf: [E_loc, C, d].

    The capacity-packed buffer is ALREADY the tile-aligned packed domain
    (uniform C rows per expert), so with use_kernel it feeds straight into
    the fused grouped-GEMM pipeline (ops.moe_ffn_packed) with no sort, no
    pack scatter and no unpack gather; otherwise a batched einsum, which is
    what XLA schedules best on non-Pallas backends.
    """
    if use_kernel:
        from repro.kernels import ops as kops  # lazy: avoid cycles
        return kops.moe_ffn_packed(buf, wi_gate.astype(cd),
                                   wi_up.astype(cd), wo.astype(cd),
                                   use_kernel=True)
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wi_gate.astype(cd)))
    u = jnp.einsum("ecd,edf->ecf", buf, wi_up.astype(cd))
    return jnp.einsum("ecf,efd->ecd", g * u, wo.astype(cd))


# ---------------------------------------------------------------------------
# Expert-parallel MoE FFN (shard_map)
# ---------------------------------------------------------------------------

def make_ep_moe(mesh: Mesh, cfg: ModelConfig, run: RunConfig,
                zcfg: ZebraConfig) -> Callable:
    """Returns moe_fn(ffn_params, x2d [T,d]) -> (y2d, aux), sharded."""
    E = cfg.n_experts
    k = cfg.top_k
    ep = zcfg.ep_axis
    n_ep = mesh.shape[ep]
    n_loc = zcfg.offload_experts if zcfg.mode == "alltoall" else 0
    E_rem = E - n_loc
    assert 0 <= n_loc < E, f"offload_experts {n_loc} out of range for E={E}"
    assert E_rem % n_ep == 0, \
        f"remote experts {E_rem} must divide over {ep}={n_ep}"
    E_loc = E_rem // n_ep
    Q = max(int(zcfg.n_chunks), 1)
    Qc = zcfg.n_chunks_combine if zcfg.n_chunks_combine \
        else (2 * Q if Q > 1 else 1)
    Qc = max(int(Qc), Q)
    assert Qc % Q == 0, \
        f"n_chunks_combine {Qc} must be a multiple of n_chunks {Q}"
    cd = run.policy.compute_dtype

    ba = tuple(zcfg.batch_axes)
    if zcfg.mode == "alltoall" and ep not in ba:
        ba = ba + (ep,)
    batch_spec = P(ba, None)
    from repro.sharding.rules import ep_ffn_specs
    ffn_specs = ep_ffn_specs(ep, offload=n_loc > 0)

    def local_route(router_w, x):
        weights, idx, aux = modules.moe_route(router_w, cfg, run.policy, x)
        # aux losses are means over the (sharded) token dim -> pmean.
        aux = {k_: jax.lax.pmean(v, ba) for k_, v in aux.items()}
        return weights, idx, aux

    if zcfg.mode == "replicated":
        def fn(ffn, x):  # x: [T_loc, d] (replicated over ep axis)
            T = x.shape[0]
            weights, idx, aux = local_route(ffn["router"], x)
            C = max(_round_up(int(T * k / E * zcfg.capacity_factor), 8), 8)
            with obs_trace.scope("dispatch"):
                my = jax.lax.axis_index(ep)
                e_off = my * E_loc
                local = (idx >= e_off) & (idx < e_off + E_loc)
                idx_loc = jnp.where(local, idx - e_off, E_loc)  # E_loc = drop
                buf, meta = _pack(x, idx_loc, E_loc + 1, C)
            with obs_trace.scope("experts"):
                out = _experts_dense(ffn["wi_gate"], ffn["wi_up"], ffn["wo"],
                                     buf[:E_loc], cd,
                                     use_kernel=run.use_gmm_kernel)
            with obs_trace.scope("combine"):
                out = jnp.concatenate(
                    [out, jnp.zeros((1, C, x.shape[1]), out.dtype)], axis=0)
                y = _unpack(out, meta, weights, T)
                y = jax.lax.psum(y, ep)  # combine partial expert outputs
            return y, aux

    else:  # alltoall: chunked, double-buffered packed-domain dispatch
        from repro.kernels import ops as kops  # lazy: avoid cycles
        # The alltoall hop always rides the ops.moe_ffn machinery (not the
        # replicated mode's batched einsum): the unified local+remote call
        # and the per-chunk slices need its tile_group metadata, and its
        # recompute-backward custom_vjp keeps only chunk INPUTS resident —
        # with n_chunks > 1 an autodiff einsum would store every chunk's
        # activations across the whole unrolled pipeline instead.
        uk = True if run.use_gmm_kernel else None  # None -> backend default

        def remote_ffn(ffn, r):
            return kops.moe_ffn_packed(r, ffn["wi_gate"].astype(cd),
                                       ffn["wi_up"].astype(cd),
                                       ffn["wo"].astype(cd), use_kernel=uk)

        def fn(ffn, x):  # x: [T_loc, d], batch sharded over ep axis as well
            T, d = x.shape
            weights, idx, aux = local_route(ffn["router"], x)
            C0 = max(_round_up(int(T * k / E * zcfg.capacity_factor), 8), 8)
            # Capacity padded so it splits into Qc equal sublane-aligned
            # COMBINE sub-chunks (pad rows are zero and inert end to end);
            # each dispatch chunk covers Qc/Q of them.
            C, Cqc = kops.chunk_capacity(C0, Qc)
            Cq = C // Q
            with obs_trace.scope("dispatch"):
                buf, meta = _pack(x, idx, E, C)  # [E, C, d] — packed
                loc = buf[:n_loc]                # local (offloaded) experts
                rem = buf[n_loc:].reshape(n_ep, E_loc, C, d)
                # Dispatch: one all-to-all per capacity chunk, all issued
                # before any expert GEMM — chunk q+1's exchange has no data
                # dependence on chunk q's compute, so the collectives hide
                # behind expert compute instead of preceding it (the
                # backward of this unrolled loop transposes chunk-by-chunk
                # and keeps the same independence, mirroring the overlap).
                recv = [jax.lax.all_to_all(
                            jax.lax.dynamic_slice_in_dim(rem, q * Cq, Cq,
                                                         axis=2),
                            ep, split_axis=0, concat_axis=0, tiled=False)
                        for q in range(Q)]
            outs = []
            for q in range(Q):
                with obs_trace.scope("experts"):
                    r = jnp.swapaxes(recv[q], 0, 1).reshape(E_loc,
                                                            n_ep * Cq, d)
                    if q == 0 and n_loc:
                        # Local + remote experts in ONE grouped GEMM per
                        # projection direction: the offloaded experts' GEMM
                        # fills the bubble while later chunks are in flight.
                        out_l, o = kops.moe_ffn_packed_multi(
                            [loc, r],
                            [ffn["wi_gate_loc"].astype(cd),
                             ffn["wi_gate"].astype(cd)],
                            [ffn["wi_up_loc"].astype(cd),
                             ffn["wi_up"].astype(cd)],
                            [ffn["wo_loc"].astype(cd), ffn["wo"].astype(cd)],
                            use_kernel=uk)
                    else:
                        o = remote_ffn(ffn, r)
                # Combine: chunk q's reverse all-to-alls are issued before
                # chunk q+1's GEMM — same hiding on the way back, at the
                # FINER combine granularity (Qc/Q sub-chunks per dispatch
                # chunk): the backward transposes these into the f32
                # cotangent dispatch, whose 2x volume is why combine
                # defaults to twice the dispatch chunk count.
                with obs_trace.scope("combine"):
                    o = jnp.swapaxes(o.reshape(E_loc, n_ep, Cq, d), 0, 1)
                    for s in range(Qc // Q):
                        outs.append(jax.lax.all_to_all(
                            o[:, :, s * Cqc:(s + 1) * Cqc], ep, split_axis=0,
                            concat_axis=0, tiled=False))
            with obs_trace.scope("combine"):
                back = outs[0] if len(outs) == 1 else \
                    jnp.concatenate(outs, axis=2)
                out_full = back.reshape(E_rem, C, d)
                if n_loc:
                    # Combine consumes ONE packed [E, C, d] output.
                    out_full = jnp.concatenate(
                        [out_l.astype(out_full.dtype), out_full], axis=0)
                y = _unpack(out_full, meta, weights, T)
            return y, aux

    in_specs = (ffn_specs, batch_spec)
    out_specs = (batch_spec, P())

    def moe_fn(ffn_params, x2d):
        fp = {"router": ffn_params["router"]}
        for k_ in ("wi_gate", "wi_up", "wo"):
            if n_loc:
                fp[k_ + "_loc"] = ffn_params[k_][:n_loc]
            fp[k_] = ffn_params[k_][n_loc:]
        sm = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        return sm(fp, x2d)

    return moe_fn


# ---------------------------------------------------------------------------
# Zebra-pipelined MoE layer (the layer_override for models/stack.py)
# ---------------------------------------------------------------------------

def make_layer_override(mesh: Mesh, cfg: ModelConfig, run: RunConfig,
                        zcfg: ZebraConfig) -> Callable:
    """Build the stack-level layer override implementing zebra parallelism."""
    moe_fn = make_ep_moe(mesh, cfg, run, zcfg)

    def override(layer_params, spec: LayerSpec, x, positions):
        B, S, d = x.shape
        R = zcfg.num_microbatches if zcfg.pipeline else 1
        while R > 1 and B % R:
            R -= 1

        def attn_part(mb_x, mb_pos):
            h, _ = modules.apply_mixer_part(layer_params, cfg, run, spec,
                                            mb_x, mb_pos)
            u = modules.apply_norm(layer_params["norm2"], h, run.policy)
            return h, u

        def expert_part(h, u):
            y2, aux = moe_fn(layer_params["ffn"], u.reshape(-1, d))
            return h + y2.reshape(h.shape).astype(h.dtype), aux

        if R == 1:
            h, u = attn_part(x, positions)
            y, aux = expert_part(h, u)
            return y, aux

        xs = x.reshape(R, B // R, S, d)
        ps = positions.reshape(R, B // R, S)

        h0, u0 = attn_part(xs[0], ps[0])

        def body(carry, inp):
            h_prev, u_prev = carry
            mb_x, mb_pos = inp
            # These two halves are data-independent: XLA overlaps the expert
            # compute + collectives of mb k-1 with attention of mb k.
            y_prev, aux = expert_part(h_prev, u_prev)
            h_k, u_k = attn_part(mb_x, mb_pos)
            return (h_k, u_k), (y_prev, aux)

        if cfg.unroll:
            carry = (h0, u0)
            ys_l, auxs_l = [], []
            for kk in range(1, R):
                carry, (y_prev, a) = body(carry, (xs[kk], ps[kk]))
                ys_l.append(y_prev)
                auxs_l.append(a)
            ys = jnp.stack(ys_l)  # R >= 2 here
            auxs = jax.tree.map(lambda *vs: jnp.stack(vs), *auxs_l)
            h_l, u_l = carry
        else:
            (h_l, u_l), (ys, auxs) = jax.lax.scan(body, (h0, u0),
                                                  (xs[1:], ps[1:]))
        y_last, aux_last = expert_part(h_l, u_l)
        y = jnp.concatenate([ys, y_last[None]], axis=0).reshape(B, S, d)
        # aux losses are per-token means: average them over microbatches.
        aux = jax.tree.map(lambda a, b: (jnp.sum(a, axis=0) + b) / R, auxs,
                           aux_last)
        return y, aux

    return override
