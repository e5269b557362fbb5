"""Pallas TPU grouped matmul (MoE expert GEMM) — MegaBlocks adapted to TPU.

MegaBlocks frames dropless-MoE expert compute as a block-sparse GEMM driven
by a CSR-like topology. TPUs have no hardware gather/CSR GEMM, so the TPU
adaptation (see DESIGN.md §5) is: the wrapper repacks expert-sorted rows so
every group starts at a tile boundary (padding each group to a multiple of
``block_m``); the kernel is then a dense tiled matmul whose *rhs* tile is
selected per m-tile through a scalar-prefetched ``tile_group`` map. Padding
rows are zero and their outputs are dropped on unpack, so no in-kernel
masking is needed; cost is <= G*(block_m-1) phantom rows.

Kernel signature:
    lhs:  [Mp, K]   rows sorted by group, group-start tile-aligned
    rhs:  [G, K, N] per-group weights
    tile_group: [Mp / block_m] int32 — group id of each m-tile (prefetched)
    out:  [Mp, N]
Accumulation over the sequential k-tile grid dim in an f32 VMEM scratch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(tile_group, lhs_ref, rhs_ref, out_ref, acc, *, n_k):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jax.lax.dot_general(
        lhs_ref[...].astype(jnp.float32), rhs_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ik == n_k - 1)
    def _finish():
        out_ref[...] = acc[...].astype(out_ref.dtype)


def gmm_tiled(lhs, rhs, tile_group, *, block_m=128, block_k=128, block_n=128,
              interpret=False, out_dtype=None):
    """Dense tiled grouped matmul over tile-aligned groups.

    lhs: [Mp, K]; rhs: [G, K, N]; tile_group: [Mp//block_m] int32.
    """
    Mp, K = lhs.shape
    G, _, N = rhs.shape
    assert Mp % block_m == 0
    # Pad K and N to tile multiples.
    pk = (-K) % block_k
    pn = (-N) % block_n
    if pk:
        lhs = jnp.pad(lhs, ((0, 0), (0, pk)))
        rhs = jnp.pad(rhs, ((0, 0), (0, pk), (0, 0)))
    if pn:
        rhs = jnp.pad(rhs, ((0, 0), (0, 0), (0, pn)))
    Kp, Np = K + pk, N + pn
    n_m, n_n, n_k = Mp // block_m, Np // block_n, Kp // block_k
    out_dtype = out_dtype or lhs.dtype

    out = pl.pallas_call(
        functools.partial(_gmm_kernel, n_k=n_k),
        name="gmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_m, n_n, n_k),
            in_specs=[
                pl.BlockSpec((block_m, block_k),
                             lambda im, jn, ik, tg: (im, ik)),
                pl.BlockSpec((1, block_k, block_n),
                             lambda im, jn, ik, tg: (tg[im], ik, jn)),
            ],
            out_specs=pl.BlockSpec((block_m, block_n),
                                   lambda im, jn, ik, tg: (im, jn)),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(tile_group, lhs, rhs)
    return out[:, :N]


# ---------------------------------------------------------------------------
# VMEM budgeting for block-size autotuning
# ---------------------------------------------------------------------------

VMEM_BUDGET_BYTES = 16 * 1024 * 1024  # per-core VMEM, v4/v5e class


def glu_vmem_bytes(block_m: int, block_k: int, block_n: int,
                   lhs_dtype=jnp.bfloat16, rhs_dtype=jnp.bfloat16) -> int:
    """Peak VMEM working set of one gmm_glu_tiled grid step.

    Streamed operands (lhs tile, gate+up rhs tiles, out tile) are
    double-buffered by the Pallas pipeline (2x); the two f32 accumulator
    scratches are single instances that live across the k-loop.
    """
    lb = jnp.dtype(lhs_dtype).itemsize
    rb = jnp.dtype(rhs_dtype).itemsize
    streamed = (block_m * block_k * lb          # lhs tile
                + 2 * block_k * block_n * rb    # gate + up rhs tiles
                + block_m * block_n * lb)       # fused output tile
    scratch = 2 * block_m * block_n * 4         # two f32 accumulators
    return 2 * streamed + scratch


def glu_block_candidates(block_k: int = 128,
                         vmem_budget: int = VMEM_BUDGET_BYTES,
                         lhs_dtype=jnp.bfloat16, rhs_dtype=jnp.bfloat16,
                         ms=(512, 256, 128, 64), ns=(512, 256, 128)):
    """(block_m, block_n) sweep candidates for gmm_glu_tiled that fit the
    VMEM budget, largest tiles first (MXU-aligned multiples of 128 plus a
    64-row sublane option for capacity-chunked buffers)."""
    out = []
    for bm in ms:
        for bn in ns:
            if glu_vmem_bytes(bm, block_k, bn, lhs_dtype,
                              rhs_dtype) <= vmem_budget:
                out.append((bm, bn))
    return out


def _gmm_glu_kernel(tile_group, lhs_ref, rhs_g_ref, rhs_u_ref, out_ref,
                    acc_g, acc_u, *, n_k):
    """Fused GLU grouped matmul: out = silu(lhs @ rhs_g) * (lhs @ rhs_u).

    Each lhs m-tile is read from HBM ONCE and feeds both the gate and the up
    GEMM; the activation (silu * mul) is applied on the f32 accumulators in
    VMEM before the single flush, so the intermediate ``g``/``u`` tensors
    never round-trip through HBM (DESIGN.md §5.3).
    """
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_g[...] = jnp.zeros_like(acc_g)
        acc_u[...] = jnp.zeros_like(acc_u)

    lhs = lhs_ref[...].astype(jnp.float32)
    acc_g[...] += jax.lax.dot_general(
        lhs, rhs_g_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    acc_u[...] += jax.lax.dot_general(
        lhs, rhs_u_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ik == n_k - 1)
    def _finish():
        g = acc_g[...]
        out_ref[...] = (g * jax.lax.logistic(g) * acc_u[...]
                        ).astype(out_ref.dtype)


def gmm_glu_tiled(lhs, rhs_stacked, tile_group, *, block_m=128, block_k=128,
                  block_n=128, interpret=False, out_dtype=None):
    """Fused GLU grouped matmul over tile-aligned groups, stacked weights.

    lhs: [Mp, K]; rhs_stacked: [G, K, 2N] (gate weights in [..., :N], up
    weights in [..., N:]); tile_group: [Mp//block_m] int32.
    Returns [Mp, N] = silu(lhs @ gate) * (lhs @ up) per group.
    """
    G, _, N2 = rhs_stacked.shape
    assert N2 % 2 == 0
    N = N2 // 2
    K = lhs.shape[1]
    if (-K) % block_k == 0 and (-N) % block_n == 0:
        # Tile-aligned halves (the production case): index straight into
        # the stacked tensor — the up tile of output column-block jn lives
        # at column-block jn + N/block_n. No slice/pad copies.
        return _gmm_glu_call(lhs, rhs_stacked, rhs_stacked, tile_group,
                             N // block_n, N, block_m=block_m,
                             block_k=block_k, block_n=block_n,
                             interpret=interpret, out_dtype=out_dtype)
    return gmm_glu_tiled_pair(lhs, rhs_stacked[:, :, :N],
                              rhs_stacked[:, :, N:], tile_group,
                              block_m=block_m, block_k=block_k,
                              block_n=block_n, interpret=interpret,
                              out_dtype=out_dtype)


def gmm_glu_tiled_pair(lhs, rhs_gate, rhs_up, tile_group, *, block_m=128,
                       block_k=128, block_n=128, interpret=False,
                       out_dtype=None):
    """gmm_glu_tiled with gate/up as separate [G, K, N] arrays — lets
    callers holding unstacked expert weights (the param layout) skip the
    [G, K, 2N] restack copy entirely."""
    K = lhs.shape[1]
    N = rhs_gate.shape[-1]
    pk = (-K) % block_k
    pn = (-N) % block_n
    if pk:
        lhs = jnp.pad(lhs, ((0, 0), (0, pk)))
        rhs_gate = jnp.pad(rhs_gate, ((0, 0), (0, pk), (0, 0)))
        rhs_up = jnp.pad(rhs_up, ((0, 0), (0, pk), (0, 0)))
    if pn:
        rhs_gate = jnp.pad(rhs_gate, ((0, 0), (0, 0), (0, pn)))
        rhs_up = jnp.pad(rhs_up, ((0, 0), (0, 0), (0, pn)))
    return _gmm_glu_call(lhs, rhs_gate, rhs_up, tile_group, 0, N,
                         block_m=block_m, block_k=block_k, block_n=block_n,
                         interpret=interpret, out_dtype=out_dtype)


def _gmm_glu_call(lhs, rhs_g, rhs_u, tile_group, u_off, N, *, block_m,
                  block_k, block_n, interpret, out_dtype):
    """Shared pallas_call: lhs/rhs already tile-padded; the up tile of
    output column-block jn is read at column-block jn + u_off of rhs_u."""
    Mp, Kp = lhs.shape
    assert Mp % block_m == 0
    Np = ((N + block_n - 1) // block_n) * block_n
    n_m, n_n, n_k = Mp // block_m, Np // block_n, Kp // block_k
    out_dtype = out_dtype or lhs.dtype

    out = pl.pallas_call(
        functools.partial(_gmm_glu_kernel, n_k=n_k),
        name="gmm_glu",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_m, n_n, n_k),
            in_specs=[
                pl.BlockSpec((block_m, block_k),
                             lambda im, jn, ik, tg: (im, ik)),
                pl.BlockSpec((1, block_k, block_n),
                             lambda im, jn, ik, tg: (tg[im], ik, jn)),
                pl.BlockSpec((1, block_k, block_n),
                             lambda im, jn, ik, tg: (tg[im], ik,
                                                     jn + u_off)),
            ],
            out_specs=pl.BlockSpec((block_m, block_n),
                                   lambda im, jn, ik, tg: (im, jn)),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32),
                            pltpu.VMEM((block_m, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(tile_group, lhs, rhs_g, rhs_u)
    return out[:, :N]


def _dw_kernel(tile_group, lhs_ref, dout_ref, drhs_ref, acc, *, n_m,
               tile_group_host=None):
    """drhs[g] = sum over that group's row tiles of lhs_tile^T @ dout_tile.

    Grid (k, n, m) with m sequential; the output block index (tg[im], k, n)
    revisits the same block for consecutive tiles of one group (groups are
    contiguous), so we zero the accumulator at each group start and flush at
    each group end (Pallas TPU output-revisiting semantics).
    """
    im = pl.program_id(2)
    first = im == 0
    if n_m > 1:
        prev = tile_group[jnp.maximum(im - 1, 0)]
        first = jnp.logical_or(first, tile_group[im] != prev)

    @pl.when(first)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jax.lax.dot_general(
        lhs_ref[...].astype(jnp.float32), dout_ref[...].astype(jnp.float32),
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    last = im == n_m - 1
    if n_m > 1:
        nxt = tile_group[jnp.minimum(im + 1, n_m - 1)]
        last = jnp.logical_or(last, tile_group[im] != nxt)

    @pl.when(last)
    def _finish():
        drhs_ref[0] = acc[...].astype(drhs_ref.dtype)


def gmm_dw_tiled(lhs, dout, tile_group, n_groups, *, block_m=128, block_k=128,
                 block_n=128, interpret=False, out_dtype=jnp.float32):
    """Gradient wrt rhs: [G, K, N] from tile-aligned lhs [Mp,K], dout [Mp,N].

    Groups with no tiles produce zero blocks (their buffers are only flushed
    if visited; we initialize via a zero-fill pass on the host side instead).
    """
    Mp, K = lhs.shape
    N = dout.shape[1]
    pk = (-K) % block_k
    pn = (-N) % block_n
    if pk:
        lhs = jnp.pad(lhs, ((0, 0), (0, pk)))
    if pn:
        dout = jnp.pad(dout, ((0, 0), (0, pn)))
    Kp, Np = K + pk, N + pn
    n_m, n_k, n_n = Mp // block_m, Kp // block_k, Np // block_n

    drhs = pl.pallas_call(
        functools.partial(_dw_kernel, n_m=n_m),
        name="gmm_dw",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_k, n_n, n_m),
            in_specs=[
                pl.BlockSpec((block_m, block_k),
                             lambda ik, jn, im, tg: (im, ik)),
                pl.BlockSpec((block_m, block_n),
                             lambda ik, jn, im, tg: (im, jn)),
            ],
            out_specs=pl.BlockSpec((1, block_k, block_n),
                                   lambda ik, jn, im, tg: (tg[im], ik, jn)),
            scratch_shapes=[pltpu.VMEM((block_k, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n_groups, Kp, Np), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(tile_group, lhs, dout)
    drhs = drhs[:, :K, :N]
    # Tiles only flush blocks they visit; a group that received zero rows
    # never flushes -> mask its (undefined) block to zero.
    visited = jnp.zeros((n_groups,), bool).at[tile_group].set(True)
    return jnp.where(visited[:, None, None], drhs, 0.0)
