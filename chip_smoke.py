"""On-chip smoke test: the training and serving main paths on a TPU.

    python chip_smoke.py             # one chip: train, kernel parity, serve
    python chip_smoke.py --chips 4   # four chips: zebra all-to-all only

One chip runs three phases, each through the entry points a user calls:

* train: mixtral-d2 at published widths (d_model 1024, 8/2 heads, 18
  experts, top-2, d_ff 3584, vocab 32000), depth cut 6 -> 3 layers,
  through ``launch.train`` on one fixed batch: first zebra replicated (the
  default mode, whose expert FFN is an XLA einsum), then ``--no-zebra``,
  whose MoE takes the Mosaic grouped-GEMM kernels on TPU. Every loss must
  be finite, step 0 within 1.0 of ln(vocab), the last loss below the
  first; the zebra step must leave >= 1.5 GB of the chip free by the
  compiler's memory analysis, and the gather step must hold a Mosaic
  kernel (``tpu_custom_call``).
* kernels: the Pallas grouped-GEMM MoE FFN (output and gradients), paged
  decode attention and flash attention (output and gradients) against
  their XLA paths, at the train phase's widths, within bf16 tolerance.
* serve: the full 6-layer mixtral-d2 through ``launch.serve.serve_arch``
  with the paged KV cache: every request finishes and the page allocator's
  accounting check passes.

``--chips 4`` trains mixtral-d1 (8 layers, 24 experts) with zebra
all-to-all on a 1x4 mesh, n_chunks 2 against n_chunks 1: step-0 losses
agree within bf16 tolerance, and each device holds 6 of the 24 experts.

The last line of standard output is one JSON object naming the device, and
is printed only when every phase passed. Without a TPU the script exits
non-zero before any work. Weights are random from ``--seed``; the compile
cache follows ``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent

# Tolerances on max|got - want| / max|want|, fixed from the dtype (bf16
# inputs, f32 accumulation) and matching the repo's bf16 kernel tests.
GMM_TOL = 5e-2
ATTN_TOL = 2e-2

# The configurations each phase drives (batch and depth chosen so the step
# fits one v5e with >= 1.5 GB to spare by the compiler's memory analysis).
TRAIN_ARGV = ["--arch", "mixtral-d2", "--n-layers", "3", "--mesh", "1x1",
              "--zebra-mode", "replicated", "--batch", "4", "--seq", "2048",
              "--steps", "8", "--log-every", "1"]
GATHER_ARGV = ["--arch", "mixtral-d2", "--n-layers", "3", "--mesh", "1x1",
               "--no-zebra", "--batch", "1", "--seq", "2048",
               "--steps", "4", "--log-every", "1"]
MIN_HEADROOM_GB = 1.5
SERVE_ARGV = ["--arch", "mixtral-d2", "--paged", "--page-size", "16",
              "--slots", "8", "--requests", "8", "--prompt-len", "256",
              "--gen", "32", "--prefill-chunk", "64"]
ZEBRA_ARGV = ["--arch", "mixtral-d1", "--mesh", "1x4",
              "--zebra-mode", "alltoall", "--batch", "4", "--seq", "2048",
              "--steps", "3", "--log-every", "1"]
# Kernel parity shapes: mixtral-d2 widths; 4096 expert rows; 8 decode slots
# of up to 24 pages; a 1024-token flash sequence.
FFN = dict(d=1024, f=3584, groups=18, rows=4096)
ATTN = dict(heads=8, kv_heads=2, head_dim=128, page_size=16, slots=8,
            max_pages=24, pool_pages=256, seq=1024)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train + kernel parity + serve on one chip; "
                         "4: zebra all-to-all on a 1x4 mesh only")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"check failed: {what}")


def rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                    1e-30))


def run_jit(fn, *args, kernel: bool):
    """Call jitted ``fn``, first checking that its compiled program holds a
    Mosaic kernel exactly when ``kernel`` says so: a parity check between
    two paths that are secretly one path compares nothing."""
    compiled = fn.lower(*args).compile()
    check(("tpu_custom_call" in compiled.as_text()) == kernel,
          f"{'a' if kernel else 'no'} Mosaic kernel in the "
          f"{'kernel' if kernel else 'reference'} path")
    return compiled(*args)


def fixed_batch_file(tmp: str, batch: int, seq: int, vocab: int,
                     seed: int) -> str:
    """A token file holding exactly one batch: the loader then serves the
    same batch at every step."""
    from repro.data.pipeline import write_token_bin
    return write_token_bin(f"{tmp}/batch.bin", batch * seq + 1, vocab,
                           seed=seed)


def train(argv: list, seed: int) -> object:
    """``launch.train`` on one fixed batch of random tokens."""
    from repro.launch import train as train_cli
    from repro.models import registry
    args = train_cli.parse_args(argv)
    vocab = registry.get_config(args.arch).vocab_size
    with tempfile.TemporaryDirectory() as tmp:
        args.data = fixed_batch_file(tmp, args.batch, args.seq, vocab, seed)
        return train_cli.run_training(args)


def memory_line(compiled, device) -> tuple:
    """(headroom in GB, a line saying what the step needs of the chip)."""
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    stats = device.memory_stats()
    limit = stats["bytes_limit"]
    headroom = (limit - need) / 1e9
    return headroom, (
        f"step needs {need / 1e9:.2f} GB (args "
        f"{ma.argument_size_in_bytes / 1e9:.2f} + temps "
        f"{ma.temp_size_in_bytes / 1e9:.2f}) of {limit / 1e9:.2f} GB, "
        f"headroom {headroom:.2f} GB; peak in use "
        f"{stats['peak_bytes_in_use'] / 1e9:.2f} GB")


# ---------------------------------------------------------------------------
# One chip
# ---------------------------------------------------------------------------

def check_losses(res, tag: str, min_steps: int) -> None:
    losses = [h["loss"] for h in res.history]
    vocab = res.cfg.vocab_size
    print(f"[smoke] {tag}: losses {losses}")
    check(len(losses) >= min_steps,
          f"{tag}: >= {min_steps} logged steps, got {len(losses)}")
    check(all(math.isfinite(x) for x in losses), f"{tag}: every loss finite")
    check(abs(losses[0] - math.log(vocab)) <= 1.0,
          f"{tag}: step-0 loss {losses[0]} within 1.0 of ln({vocab})")
    check(losses[-1] < losses[0],
          f"{tag}: last loss {losses[-1]} below first {losses[0]}")


def phase_train(seed: int) -> None:
    import jax
    res = train(TRAIN_ARGV, seed)
    headroom, line = memory_line(res.compiled, jax.devices()[0])
    print(f"[smoke] train zebra: {line}")
    check_losses(res, "train zebra", 6)
    check(headroom >= MIN_HEADROOM_GB,
          f"zebra step leaves >= {MIN_HEADROOM_GB} GB free")
    del res
    res = train(GATHER_ARGV, seed)
    _, line = memory_line(res.compiled, jax.devices()[0])
    print(f"[smoke] train gather: {line}")
    check_losses(res, "train gather", 4)
    check("tpu_custom_call" in res.compiled.as_text(),
          "compiled gather train step holds a Mosaic kernel")


def phase_kernels(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def normal(shape, scale=1.0, dtype=jnp.bfloat16):
        return (scale * jax.random.normal(next(keys), shape)).astype(dtype)

    # Grouped-GEMM MoE FFN at the train phase's widths, uneven groups
    # with one empty.
    d, f, G, M = FFN["d"], FFN["f"], FFN["groups"], FFN["rows"]
    rng = np.random.default_rng(seed)
    sizes = rng.multinomial(M, rng.dirichlet(np.ones(G)))
    sizes[3] += sizes[5]
    sizes[5] = 0
    gs = jnp.asarray(sizes, jnp.int32)
    x = normal((M, d))
    wg, wu = normal((G, d, f), d ** -0.5), normal((G, d, f), d ** -0.5)
    wo = normal((G, f, d), f ** -0.5)
    r = normal((M, d), dtype=jnp.float32)

    def ffn_loss(use_kernel):
        def loss(x, wg, wu, wo):
            y = ops.moe_ffn(x, wg, wu, wo, gs, use_kernel=use_kernel,
                            small_m=False)
            return jnp.sum(y.astype(jnp.float32) * r), y
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3),
                                          has_aux=True))

    (_, y_k), g_k = run_jit(ffn_loss(True), x, wg, wu, wo, kernel=True)
    (_, y_x), g_x = run_jit(ffn_loss(False), x, wg, wu, wo, kernel=False)
    errs = [rel_err(y_k, y_x)] + [rel_err(a, b) for a, b in zip(g_k, g_x)]
    print(f"[smoke] kernels: moe_ffn out/dx/dwg/dwu/dwo rel err {errs}")
    check(max(errs) <= GMM_TOL, f"moe_ffn kernel within {GMM_TOL}")

    # Paged decode: 8 slots over a shuffled pool, one dead slot, mixtral-d2
    # heads (8 query / 2 KV, head_dim 128), page_size 16.
    B, H, KH, hd = (ATTN["slots"], ATTN["heads"], ATTN["kv_heads"],
                    ATTN["head_dim"])
    ps, MP, P = ATTN["page_size"], ATTN["max_pages"], ATTN["pool_pages"]
    q = normal((B, H, hd))
    k_pool, v_pool = normal((P, ps, KH, hd)), normal((P, ps, KH, hd))
    q_pos = rng.integers(0, MP * ps, size=B)
    q_pos[2] = -1
    pages = rng.permutation(P)[:B * MP].reshape(B, MP)
    table = np.where(np.arange(MP)[None] * ps <= np.maximum(q_pos, 0)[:, None],
                     pages, -1)
    table, q_pos = jnp.asarray(table, jnp.int32), jnp.asarray(q_pos, jnp.int32)
    outs = [run_jit(jax.jit(lambda *a, uk=uk: ops.paged_decode_attention(
        *a, use_kernel=uk)), q, k_pool, v_pool, table, q_pos, kernel=uk)
        for uk in (True, False)]
    err = rel_err(*outs)
    print(f"[smoke] kernels: paged decode rel err {err}")
    check(err <= ATTN_TOL, f"paged decode kernel within {ATTN_TOL}")

    # Flash attention forward and backward against the reference.
    S = ATTN["seq"]
    qf, kf, vf = normal((1, S, H, hd)), normal((1, S, KH, hd)), \
        normal((1, S, KH, hd))
    rf = normal((1, S, H, hd), dtype=jnp.float32)
    mask = ref.causal_window_mask(S, S, True, 0)

    def attn_loss(flash):
        def loss(q, k, v):
            if flash:
                o = ops.flash_attention(q, k, v, causal=True)
            else:
                o = ref.attention(q.astype(jnp.float32),
                                  k.astype(jnp.float32),
                                  v.astype(jnp.float32), mask=mask)
            return jnp.sum(o.astype(jnp.float32) * rf), o
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))

    (_, o_k), g_k = run_jit(attn_loss(True), qf, kf, vf, kernel=True)
    (_, o_r), g_r = run_jit(attn_loss(False), qf, kf, vf, kernel=False)
    errs = [rel_err(o_k, o_r)] + [rel_err(a, b) for a, b in zip(g_k, g_r)]
    print(f"[smoke] kernels: flash out/dq/dk/dv rel err {errs}")
    check(max(errs) <= ATTN_TOL, f"flash kernel within {ATTN_TOL}")


def phase_serve(seed: int) -> None:
    import numpy as np
    from repro.launch import serve as serve_cli
    from repro.models import registry
    from repro.serve import Request

    args = serve_cli.parse_args(SERVE_ARGV + ["--seed", str(seed)])
    vocab = registry.get_config(args.arch).vocab_size
    # Prompt lengths are whole prefill chunks, so one prefill program
    # serves every chunk.
    rng = np.random.default_rng(seed)
    trace = [Request(rid=i, prompt=rng.integers(
                         0, vocab,
                         size=args.prefill_chunk * (1 + i % 4)).tolist(),
                     max_new_tokens=args.gen, arrival=float(i))
             for i in range(args.requests)]
    check(max(len(r.prompt) for r in trace) <= args.prompt_len,
          "prompts fit the deployment's max length")
    s = serve_cli.serve_arch(args.arch, args, trace=trace)
    check(s.get("ok", False), "every request finished, allocator clean")
    check(s["n_requests"] == args.requests,
          f"{args.requests} requests finished")
    check(s["n_generated_tokens"] == args.requests * args.gen,
          f"{args.requests * args.gen} tokens generated")
    check("paged" in s, "paged deployment served")


# ---------------------------------------------------------------------------
# Four chips
# ---------------------------------------------------------------------------

def phase_zebra_a2a(seed: int) -> None:
    import jax
    step0 = {}
    for n_chunks in (1, 2):
        res = train(ZEBRA_ARGV + ["--n-chunks", str(n_chunks)], seed)
        tag = f"[smoke] zebra n_chunks={n_chunks}"
        print(f"{tag}: {memory_line(res.compiled, jax.devices()[0])[1]}")
        losses = [h["loss"] for h in res.history]
        check(all(math.isfinite(x) for x in losses),
              f"n_chunks={n_chunks}: every loss finite")
        check("all-to-all" in res.compiled.as_text(),
              f"n_chunks={n_chunks}: step holds all-to-alls")
        n_exp = res.cfg.n_experts
        placement = expert_placement(res.params, n_exp)
        print(f"{tag}: experts per device {placement}")
        check(len(placement) == 4
              and all(n == n_exp // 4 for n in placement.values()),
              f"n_chunks={n_chunks}: {n_exp // 4} experts on each of 4 "
              f"devices")
        step0[n_chunks] = losses[0]
        del res
    print(f"[smoke] zebra step-0 loss n_chunks=1 {step0[1]} "
          f"n_chunks=2 {step0[2]}")
    check(abs(step0[1] - step0[2]) <= 1e-2 * abs(step0[1]),
          "n_chunks 1 and 2 agree at step 0 within bf16 tolerance")


def expert_placement(params, n_experts: int) -> dict:
    """Device id -> experts held, over the shards of the first expert
    weight stack (its first axis of size ``n_experts``)."""
    from repro.pytree import tree_map_with_path_names
    found = []
    tree_map_with_path_names(
        lambda name, x: found.append(x) if "wi_gate" in name else None,
        params)
    check(bool(found), "an expert weight stack exists")
    w = found[0]
    axis = w.shape.index(n_experts)
    per_dev = {}
    for shard in w.addressable_shards:
        per_dev[shard.device.id] = per_dev.get(shard.device.id, 0) \
            + shard.data.shape[axis]
    return per_dev


def main(argv=None) -> int:
    args = parse_args(argv)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"[smoke] no TPU: JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"[smoke] --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.cache import enable_compile_cache
    print(f"[smoke] {devices[0].device_kind} x{len(devices)}, compile cache "
          f"{enable_compile_cache()}", flush=True)

    phases = ([phase_train, phase_kernels, phase_serve] if args.chips == 1
              else [phase_zebra_a2a])
    for phase in phases:
        t0 = time.perf_counter()
        phase(args.seed)
        print(f"[smoke] {phase.__name__} passed in "
              f"{time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
