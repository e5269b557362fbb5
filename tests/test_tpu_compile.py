"""Main-path Pallas kernels compile for TPU v5e, with no chip attached.

Each test lowers a kernel at published widths (mixtral-d2: d_model 1024,
d_ff 3584, 18 experts, KV heads 2, head_dim 128, page_size 16) against a
described ``v5e:2x2`` topology and compiles it with the chip's own compiler
(Mosaic). That catches what interpret mode passes: block shapes that break
the (8, 128) tiling rule, VMEM overruns, unaligned slices. Nothing runs.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every pytest-xdist worker
imports this module. All such compiles stay in this one file.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

D_MODEL, D_FF, N_EXPERTS = 1024, 3584, 18
N_HEADS, N_KV_HEADS, HEAD_DIM, PAGE_SIZE = 8, 2, 128, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these tests.
    was = jax.config.jax_enable_compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs go to /tmp
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernels(text: str) -> set:
    """Names of the Mosaic kernels in compiled HLO text: each
    ``pallas_call(name=...)`` becomes the name of its custom-call, which
    is what a profile's op events carry; autodiff wraps it
    (``transpose_jvp_flash_dq__``)."""
    return {re.sub(r"^(?:transpose_|jvp_)+", "", m.group(1)).rstrip("_")
            for m in re.finditer(
                r"%([A-Za-z_]+)(?:\.\d+)? = [^\n]*custom_call_target="
                r'"tpu_custom_call"', text)}


def test_moe_ffn_fwd_and_grad_compile(one_chip):
    M = 4 * 2048 * 2  # train-phase rows: 4 x 2048 tokens, top-2
    bf = jnp.bfloat16
    x = _spec((M, D_MODEL), bf, one_chip)
    wg = _spec((N_EXPERTS, D_MODEL, D_FF), bf, one_chip)
    wu = _spec((N_EXPERTS, D_MODEL, D_FF), bf, one_chip)
    wo = _spec((N_EXPERTS, D_FF, D_MODEL), bf, one_chip)
    gs = _spec((N_EXPERTS,), jnp.int32, one_chip)

    def fwd(x, wg, wu, wo, gs):
        return ops.moe_ffn(x, wg, wu, wo, gs, use_kernel=True,
                           interpret=False, small_m=False)

    def loss(x, wg, wu, wo, gs):
        return jnp.sum(fwd(x, wg, wu, wo, gs).astype(jnp.float32) ** 2)

    text = _compiled_text(fwd, x, wg, wu, wo, gs)
    assert "tpu_custom_call" in text
    assert _kernels(text) == {"gmm_glu", "gmm"}
    grad = jax.grad(loss, argnums=(0, 1, 2, 3))
    text = _compiled_text(grad, x, wg, wu, wo, gs)
    assert "tpu_custom_call" in text
    assert {"gmm_glu", "gmm", "gmm_dw"} <= _kernels(text)


@pytest.mark.parametrize("kv_heads", [2, 4, 8])
def test_paged_decode_compile(one_chip, kv_heads):
    B, P, MP = 8, 256, 32
    bf = jnp.bfloat16
    q = _spec((B, N_HEADS, HEAD_DIM), bf, one_chip)
    pool = _spec((P, PAGE_SIZE, kv_heads, HEAD_DIM), bf, one_chip)
    table = _spec((B, MP), jnp.int32, one_chip)
    q_pos = _spec((B,), jnp.int32, one_chip)

    def decode(q, k, v, table, q_pos):
        return ops.paged_decode_attention(q, k, v, table, q_pos,
                                          use_kernel=True, interpret=False)

    text = _compiled_text(decode, q, pool, pool, table, q_pos)
    assert "tpu_custom_call" in text
    assert _kernels(text) == {"paged_decode"}


def test_flash_fwd_and_bwd_compile(one_chip):
    B, S = 1, 1024
    bf = jnp.bfloat16
    q = _spec((B, S, N_HEADS, HEAD_DIM), bf, one_chip)
    kv = _spec((B, S, N_KV_HEADS, HEAD_DIM), bf, one_chip)

    def fwd(q, k, v):
        return ops.flash_attention(q, k, v, causal=True, interpret=False)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32) ** 2)

    text = _compiled_text(fwd, q, kv, kv)
    assert "tpu_custom_call" in text
    assert _kernels(text) == {"flash_fwd"}
    grad = jax.grad(loss, argnums=(0, 1, 2))
    text = _compiled_text(grad, q, kv, kv)
    assert "tpu_custom_call" in text
    assert _kernels(text) == {"flash_fwd", "flash_dq", "flash_dkv"}
