"""Expert-parallel decode with heterogeneity-aware placement (DESIGN.md §11).

The replicated serving engines keep every expert's weights on every decode
device — exactly the per-device HBM that paged KV (§9) and disaggregation
(§10) were built to conserve. This module shards the expert stacks across
the decode group and routes decode tokens through the same chunked
all-to-all machinery the zebra training engines use (§8), so per-device
expert weight residency drops by ``ep_size``× while the decode step stays
greedy token-exact vs the replicated engine.

Placement is data, not layout: experts are stored in PACKED order (shard
j's experts occupy slots ``[j*E_loc, (j+1)*E_loc)`` of the expert axis) and
an ``eslot`` int32 map — injected next to each MoE ffn's weights — carries
expert-id -> slot. Re-placing experts (hot -> strong device class, cold ->
weak, per the observed routing histogram) is then a host-side permutation
of the weight stacks + a new ``eslot``: page tables, KV pools and slot
state never move, which is what makes the online re-balance token-exact
mid-trace.

Routing histograms come back from the decode step itself: the EP MoE hop
counts routed copies per GLOBAL expert id (dead slots masked out) and the
stack surfaces them per layer via ``aux_extras`` / ``layer_aux``; the
engine feeds them to :class:`~repro.serve.metrics.RoutingEMA` and triggers
``rebalance`` when the distribution drifts.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.asym_ea import asym_ea_place, round_robin_placement
from repro.core.zebra_spmd import _pack, _round_up, _unpack
from repro.models import modules
from repro.models.config import ModelConfig
from repro.models.modules import RunConfig
from repro.serve.engine import ContinuousBatchingEngine, ContinuousProgram
from repro.serve.metrics import RoutingEMA
from repro.serve.scheduler import Scheduler


@dataclasses.dataclass(frozen=True)
class EPDecodeConfig:
    """Expert-parallel decode configuration (DESIGN.md §11).

    ep_size must equal the mesh's ``ep_axis`` extent and divide the expert
    count — validation REJECTS a non-dividing ep_size (no silent
    truncation; the launch driver surfaces the ValueError as a non-zero
    exit). ``placement`` is the initial expert -> shard assignment
    (defaults to round-robin); ``rebalance_every`` > 0 checks the routing
    EMA's drift every that many decode steps and re-places experts when
    total-variation drift exceeds ``drift_threshold``.
    """

    ep_size: int
    ep_axis: str = "model"
    n_chunks: int = 1           # chunked a2a dispatch (zebra §8 semantics)
    placement: Optional[tuple] = None
    rebalance_every: int = 0    # decode steps between drift checks; 0 = off
    drift_threshold: float = 0.1
    ema_decay: float = 0.9


def validate_ep_config(cfg: ModelConfig, mesh: Mesh,
                       ep: EPDecodeConfig) -> None:
    """Reject-don't-truncate sanitization (cf. train/step.py's zcfg
    clamping — serving has no safe fallback, a wrong shard count silently
    changes which weights each device holds)."""
    if not cfg.is_moe:
        raise ValueError("EP decode needs a MoE model (n_experts == 0)")
    if ep.ep_size < 1:
        raise ValueError(f"ep_size must be >= 1, got {ep.ep_size}")
    if cfg.n_experts % ep.ep_size:
        raise ValueError(
            f"ep_size {ep.ep_size} does not divide n_experts "
            f"{cfg.n_experts}; refusing to truncate the expert shard")
    if ep.ep_axis not in mesh.axis_names:
        raise ValueError(f"mesh has no axis {ep.ep_axis!r}")
    if mesh.shape[ep.ep_axis] != ep.ep_size:
        raise ValueError(
            f"ep_size {ep.ep_size} != mesh axis {ep.ep_axis!r} size "
            f"{mesh.shape[ep.ep_axis]}")
    if ep.n_chunks < 1:
        raise ValueError(f"n_chunks must be >= 1, got {ep.n_chunks}")
    if ep.placement is not None:
        placement_to_perm(ep.placement, cfg.n_experts, ep.ep_size)


# ---------------------------------------------------------------------------
# Placement as data: packed permutation + expert -> slot map
# ---------------------------------------------------------------------------

def placement_to_perm(placement, n_experts: int, ep_size: int) -> tuple:
    """Validate a placement (tuple of per-shard expert-id tuples) and
    return the packed slot -> expert permutation."""
    if len(placement) != ep_size:
        raise ValueError(f"placement has {len(placement)} shards, "
                         f"expected {ep_size}")
    cap = n_experts // ep_size
    perm = []
    for j, shard in enumerate(placement):
        if len(shard) != cap:
            raise ValueError(f"shard {j} holds {len(shard)} experts, "
                             f"expected {cap} (equal cardinality)")
        perm.extend(int(e) for e in shard)
    if sorted(perm) != list(range(n_experts)):
        raise ValueError("placement is not a permutation of expert ids")
    return tuple(perm)


def eslot_of(placement, n_experts: int) -> np.ndarray:
    """Inverse permutation: expert id -> packed slot index [E] int32."""
    perm = [int(e) for shard in placement for e in shard]
    eslot = np.zeros((n_experts,), np.int32)
    eslot[np.asarray(perm)] = np.arange(n_experts, dtype=np.int32)
    return eslot


def place_params(params, cfg: ModelConfig, placement):
    """Permute every MoE ffn's expert stacks into packed placement order
    and inject the ``eslot`` map. Routers are NOT permuted — routing stays
    in global expert ids; only the storage order changes. Stacked block
    leaves ([L, E, ...]) permute axis 1 and get a broadcast [L, E] eslot
    (the scan slices it per layer); tail leaves permute axis 0."""
    perm = placement_to_perm(placement, cfg.n_experts, len(placement))
    perm_j = jnp.asarray(perm, jnp.int32)
    eslot = jnp.asarray(eslot_of(placement, cfg.n_experts))

    def walk(node):
        if isinstance(node, dict):
            if "router" in node and "wi_gate" in node:
                out = dict(node)
                stacked = jnp.ndim(node["wi_gate"]) == 4
                ax = 1 if stacked else 0
                for k in ("wi_gate", "wi_up", "wo"):
                    out[k] = jnp.take(node[k], perm_j, axis=ax)
                es = eslot
                if stacked:
                    es = jnp.broadcast_to(
                        es[None], (node["wi_gate"].shape[0],
                                   cfg.n_experts))
                out["eslot"] = es
                return out
            return {k: walk(v) for k, v in node.items()}
        return node

    return walk(params)


def ep_param_shardings(psh, pshapes, mesh: Mesh, ep: EPDecodeConfig):
    """Patch the serve param shardings: expert stacks pinned to the EP
    axis (the HBM win — each device stores E/ep_size experts) and the
    ``eslot`` map added replicated, matching ``place_params`` output."""
    ax = ep.ep_axis

    def walk(sh, shp):
        if isinstance(sh, dict):
            if "router" in sh and "wi_gate" in sh:
                out = dict(sh)
                nd = len(shp["wi_gate"].shape)
                lead = (None,) * (nd - 3)
                for k in ("wi_gate", "wi_up", "wo"):
                    out[k] = NamedSharding(mesh, P(*lead, ax, None, None))
                out["eslot"] = NamedSharding(
                    mesh, P(*((None,) * (nd - 2))))
                return out
            return {k: walk(sh[k], shp[k]) for k in sh}
        return sh

    return walk(psh, pshapes)


# ---------------------------------------------------------------------------
# The EP decode expert hop (shard_map)
# ---------------------------------------------------------------------------

def make_ep_moe_decode(mesh: Mesh, cfg: ModelConfig, run: RunConfig,
                       ep: EPDecodeConfig) -> Callable:
    """Returns ``moe_fn(ffn_params, x2d [T,d], mask [T]) -> (y2d, aux)``.

    Decode batches are tiny, so unlike the training zebra hop the token
    batch stays REPLICATED across the EP axis (divisibility-safe for any
    slot count / prefill chunk): every shard routes the full batch, then
    takes its own ceil(T/ep_size) token stripe, capacity-packs it against
    the PLACEMENT slot order (``eslot[idx]``), and exchanges capacity
    chunks with ``lax.all_to_all`` exactly like zebra's alltoall mode.
    The per-shard grouped FFN auto-routes to the group-dense small-M path
    (ops.moe_ffn_packed_multi, small_m=None) — the crossover is evaluated
    at the per-shard group count E/ep_size by construction. Stripe results
    are all-gathered back to the replicated layout.

    aux carries ``ep_counts`` [E]: routed copies per GLOBAL expert id with
    ``mask`` (the live-slot mask) applied — the RoutingEMA's input.
    """
    E = cfg.n_experts
    k = cfg.top_k
    ax = ep.ep_axis
    n_ep = ep.ep_size
    E_loc = E // n_ep
    Q = max(int(ep.n_chunks), 1)
    cd = run.policy.compute_dtype
    from repro.kernels import ops as kops
    from repro.sharding.rules import ep_ffn_specs
    uk = True if run.use_gmm_kernel else None

    ffn_specs = dict(ep_ffn_specs(ax), eslot=P(None))
    in_specs = (ffn_specs, P(None, None), P(None))
    out_specs = (P(None, None),
                 {"moe_aux_loss": P(), "moe_z_loss": P(),
                  "ep_counts": P(None)})

    def fn(ffn, x, mask):
        T, d = x.shape
        weights, idx, aux = modules.moe_route(ffn["router"], cfg,
                                              run.policy, x)
        # Routed-copy histogram in GLOBAL ids, dead slots masked out.
        # x is replicated over the EP axis, so counts (and the router aux
        # losses) are identical on every shard — no psum needed.
        counts = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(
            jnp.repeat(mask.astype(jnp.float32), k))
        aux = dict(aux, ep_counts=counts)
        # Placement remap: route in expert ids, dispatch in slot ids.
        slot_idx = jnp.take(ffn["eslot"].astype(jnp.int32), idx)
        my = jax.lax.axis_index(ax)
        Tp = -(-T // n_ep)
        pad = n_ep * Tp - T
        if pad:
            # Pad rows are zero -> zero FFN output -> inert in the combine.
            x = jnp.concatenate([x, jnp.zeros((pad, d), x.dtype)])
            slot_idx = jnp.concatenate(
                [slot_idx, jnp.zeros((pad, k), slot_idx.dtype)])
            weights = jnp.concatenate(
                [weights, jnp.zeros((pad, k), weights.dtype)])
        x_s = jax.lax.dynamic_slice_in_dim(x, my * Tp, Tp, axis=0)
        i_s = jax.lax.dynamic_slice_in_dim(slot_idx, my * Tp, Tp, axis=0)
        w_s = jax.lax.dynamic_slice_in_dim(weights, my * Tp, Tp, axis=0)
        # Dropless: top-k experts are distinct per token, so one expert
        # receives at most Tp copies from this stripe -> C >= Tp suffices.
        C, Cq = kops.chunk_capacity(max(_round_up(Tp, 8), 8), Q)
        buf, meta = _pack(x_s, i_s, E, C)       # [E, C, d], slot order
        rem = buf.reshape(n_ep, E_loc, C, d)
        recv = [jax.lax.all_to_all(
                    jax.lax.dynamic_slice_in_dim(rem, q * Cq, Cq, axis=2),
                    ax, split_axis=0, concat_axis=0, tiled=False)
                for q in range(Q)]
        outs = []
        for q in range(Q):
            r = jnp.swapaxes(recv[q], 0, 1).reshape(E_loc, n_ep * Cq, d)
            # small_m=None: auto-route on the PER-SHARD group count E_loc
            # (decode M is tiny -> group-dense, DESIGN.md §5.5).
            o = kops.moe_ffn_packed_multi(
                [r], [ffn["wi_gate"].astype(cd)],
                [ffn["wi_up"].astype(cd)], [ffn["wo"].astype(cd)],
                small_m=None, use_kernel=uk)[0]
            o = jnp.swapaxes(o.reshape(E_loc, n_ep, Cq, d), 0, 1)
            outs.append(jax.lax.all_to_all(o, ax, split_axis=0,
                                           concat_axis=0, tiled=False))
        back = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=2)
        y_s = _unpack(back.reshape(E, C, d), meta, w_s, Tp)
        y = jax.lax.all_gather(y_s, ax, axis=0, tiled=True)[:T]
        return y, aux

    def moe_fn(ffn_params, x2d, mask):
        fp = {k_: ffn_params[k_]
              for k_ in ("router", "wi_gate", "wi_up", "wo", "eslot")}
        sm = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        return sm(fp, x2d, mask)

    return moe_fn


def moe_override_for(moe_fn: Callable, active=None) -> Callable:
    """Adapt the EP moe_fn to the stack's ``moe_override`` contract.

    ``active`` is the decode step's live-slot mask [B] (traced — the
    override is built per decode call); None means every row is live
    (prefill), so the histogram counts prefill tokens at full weight there
    — but prefill never registers ``ep_counts`` in its aux accumulator,
    so only decode feeds the EMA."""
    def override(ffn_params, u):
        B, S, d = u.shape
        if active is None:
            m = jnp.ones((B * S,), jnp.float32)
        else:
            m = jnp.repeat(active.astype(jnp.float32), S)
        y2, aux = moe_fn(ffn_params, u.reshape(-1, d), m)
        return y2.reshape(u.shape).astype(u.dtype), aux
    return override


# ---------------------------------------------------------------------------
# Per-device HBM accounting (admission inputs, DESIGN.md §11.3)
# ---------------------------------------------------------------------------

def expert_weight_bytes(cfg: ModelConfig, dtype_bytes: int = 2) -> int:
    """Exact expert-stack residency (wi_gate + wi_up + wo over every MoE
    layer) from the abstract param tree."""
    from repro.train.step import abstract_params
    shapes, _ = abstract_params(cfg)
    total = 0

    def walk(node):
        nonlocal total
        if isinstance(node, dict):
            if "router" in node and "wi_gate" in node:
                for k in ("wi_gate", "wi_up", "wo"):
                    total += int(np.prod(node[k].shape))
            else:
                for v in node.values():
                    walk(v)

    walk(shapes)
    return total * dtype_bytes


def model_weight_bytes(cfg: ModelConfig, dtype_bytes: int = 2) -> int:
    from repro.train.step import abstract_params
    shapes, _ = abstract_params(cfg)
    return sum(int(np.prod(l.shape))
               for l in jax.tree.leaves(shapes)) * dtype_bytes


def ep_hbm_budget(cfg: ModelConfig, *, hbm_bytes: int, ep_size: int,
                  page_size: int, dtype_bytes: int = 2) -> dict:
    """Admission vs per-device HBM: what EP sharding frees and how many
    decode pool pages fit in it. The scheduler's pool (`BlockAllocator`
    geometry) should be sized from ``pool_pages_ep`` — replicated expert
    weights were previously charged against the same budget."""
    from repro.core import profiler as prof
    experts = expert_weight_bytes(cfg, dtype_bytes)
    dense = model_weight_bytes(cfg, dtype_bytes) - experts
    shard = -(-experts // max(ep_size, 1))
    page = max(prof.kv_page_bytes(cfg, page_size), 1)

    def pages(resident):
        return max(int((hbm_bytes - resident) // page), 0)

    return {
        "expert_bytes_total": experts,
        "expert_bytes_per_device": shard,
        "hbm_reduction": experts / max(shard, 1),
        "pool_pages_replicated": pages(dense + experts),
        "pool_pages_ep": pages(dense + shard),
    }


# ---------------------------------------------------------------------------
# EP continuous-batching engine: placement lifecycle + online re-balance
# ---------------------------------------------------------------------------

def balanced_placement(hist, ep_size: int, speeds=None) -> tuple:
    """Histogram-aware placement via the serving Asym-EA extension:
    greedy LPT over per-expert load with fixed shard cardinality. Equal
    ``speeds`` (the engine-internal default — it has no device classes)
    load-balances; the planner passes per-shard HBM bandwidths to get the
    hot-on-strong / cold-on-weak heterogeneity-aware assignment."""
    E = len(hist)
    if E % ep_size:
        raise ValueError(f"{ep_size} shards do not divide {E} experts")
    sp = list(speeds) if speeds is not None else [1.0] * ep_size
    return asym_ea_place([float(h) for h in hist], sp, E // ep_size)


class EPContinuousBatchingEngine(ContinuousBatchingEngine):
    """Continuous batching over EP-sharded expert weights (DESIGN.md §11).

    Takes UNPLACED (replicated-layout) params: placement happens here —
    permute + inject ``eslot`` + device_put under the program's EP param
    shardings. Every decode step returns the routed-copy histogram, which
    feeds a :class:`RoutingEMA`; when ``rebalance_every`` is set and the
    EMA drifts past ``drift_threshold`` (total variation vs the histogram
    the current placement was computed from), experts are re-placed via
    ``placer`` (a callable hist -> placement; defaults to load-balanced
    :func:`balanced_placement`). Re-balance swaps ONLY ``self.params`` —
    KV pools, page tables and slot state are untouched, so generation
    continues token-exact across the reshuffle.
    """

    def __init__(self, program: ContinuousProgram, params,
                 scheduler: Scheduler, *, placement=None,
                 placer: Callable = None, **kw):
        ep = program.ep
        assert ep is not None, "program was built without ep=EPDecodeConfig"
        self.epcfg = ep
        self._base_params = params
        self.placer = placer
        self.ema = RoutingEMA(program.cfg.n_experts, decay=ep.ema_decay)
        self.n_rebalances = 0
        self._steps_since_check = 0
        pl = placement if placement is not None else ep.placement
        if pl is None:
            pl = round_robin_placement(program.cfg.n_experts, ep.ep_size)
        self.placement = tuple(tuple(int(e) for e in s) for s in pl)
        E = program.cfg.n_experts
        self._placement_hist = np.full((E,), 1.0 / E)
        self._program = program  # _place runs before super().__init__
        placed = self._place(self.placement)
        super().__init__(program, placed, scheduler, **kw)

    def _place(self, placement):
        placed = place_params(self._base_params, self._program.cfg,
                              placement)
        with self._program.mesh:
            return jax.device_put(placed, self._program.param_shardings)

    def _on_ep_counts(self, counts) -> None:
        self.ema.update(np.asarray(counts))
        ep = self.epcfg
        if ep.rebalance_every <= 0:
            return
        self._steps_since_check += 1
        if self._steps_since_check < ep.rebalance_every:
            return
        self._steps_since_check = 0
        if self.ema.drift(self._placement_hist) <= ep.drift_threshold:
            return
        hist = self.ema.merged()
        new = self.placer(hist) if self.placer \
            else balanced_placement(hist, ep.ep_size)
        self.rebalance(new)

    def rebalance(self, placement) -> bool:
        """Re-place experts mid-trace. Only the param tree moves; decode
        state survives, so live requests continue token-exact."""
        placement = tuple(tuple(int(e) for e in s) for s in placement)
        self._placement_hist = self.ema.merged()
        if placement == self.placement:
            return False
        self.params = self._place(placement)
        self.placement = placement
        self.n_rebalances += 1
        return True
