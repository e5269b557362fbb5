"""Training driver: the compiled zebra train step of ``launch.train``, fed
by ``repro.data.DataLoader`` one batch per step.

Set-up builds one compiled step with its state and drives it from the
seed through ``check_steps`` steps on distinct batches; those steps are
what the reference replays. The same step and state then run the window:
each step loads and places its batch and is dispatched; the host waits
only for the step dispatched ``AHEAD_S`` seconds of steps before it, so
the chip stays fed while the host stands still. When the window's time is
up nothing more is dispatched, every step sent is waited for, and the
window ends then: all the steps count, over all of that time.

What is compared (each against the reference, which runs after the
window): every checked step's loss; per leaf, the norm of the first
gradient as the optimizer took it (its first moment after one step over
1 - b1); per leaf, the norm of the weights' change over the checked steps;
per leaf, the norm of the difference of the two first gradients. A leaf's
gap is |program - reference| (or the norm of the difference) over the
larger of the reference's norm of that leaf and of the median leaf.
Leaves whose reference gradient is under 1e-3 of the median leaf's move
by round-off alone under Adam and are left out of the change.

A gap of norms sees rounding only in second order; the difference of the
gradients sees it in first order, and so tells the configuration's
bfloat16 from a lower precision.
"""

from __future__ import annotations

import collections
import math
import time
import types

import numpy as np

import harness

# Seconds of steps kept dispatched ahead of the one the host waits for.
AHEAD_S = 5.0


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def capacity_groups(config: dict, mix: dict) -> tuple:
    """(groups, capacity) of the job's expert capacity, as the zebra
    engine (``core/zebra_spmd.py::make_ep_moe``) packs it.

    A group is one microbatch on one batch shard: the batch is split over
    the mesh's ``data`` axis, and in ``alltoall`` mode over its expert
    (``model``) axis too. The groups run microbatch-major, then shard, so
    each is a contiguous run of the batch's rows. Each expert takes
    C0 = max(round_up(int(T * k / E * capacity_factor), 8), 8) of a group's
    T tokens. In ``alltoall`` mode C0 is padded so that it splits into Qc
    combine slices of a multiple of 8 rows each (``kernels/ops.py::
    chunk_capacity``), Qc being the engine's default: 2 * n_chunks where
    that is over 1, else 1; the padded rows take tokens like any other."""
    z = mix["zebra"]
    a2a = z["mode"] == "alltoall"
    groups = z["num_microbatches"] * mix["mesh"][0] * (
        mix["mesh"][1] if a2a else 1)
    T = mix["batch"] * mix["seq"] // groups
    C = max(_round_up(int(T * config["top_k"] / config["n_experts"]
                          * config["capacity_factor"]), 8), 8)
    if a2a:
        Q = max(z.get("n_chunks", 1), 1)
        Qc = 2 * Q if Q > 1 else 1
        C = _round_up(max(-(-C // Qc), 1), 8) * Qc
    return groups, C


def dropped_shard(config: dict, mix: dict) -> tuple:
    """The experts (lo, hi) that one chip holds: the last of the mesh's
    expert (``model``) axis."""
    E = config["n_experts"]
    return E - E // mix["mesh"][1], E


def build(cell: harness.Cell, devices, spans, policy=None):
    """The train program, its seeded init and the batch feed. ``policy``
    replaces the configuration's precisions (tests only)."""
    import jax

    from repro.core.zebra_spmd import ZebraConfig
    from repro.models import stack
    from repro.models.config import ShapeConfig
    from repro.models.modules import Policy, RunConfig
    from repro.pytree import cast_tree, split_params
    from repro.train import optimizer as opt
    from repro.train.step import make_train_program

    m, mix = cell.config, cell.mix
    cfg = harness.model_config(m)
    mesh = harness.make_mesh(mix["mesh"], devices)
    run_cfg = RunConfig(policy=policy or Policy(), attn_impl="chunked",
                        moe_impl="gather", remat="full")
    z = mix["zebra"]
    zcfg = ZebraConfig(mode=z["mode"], num_microbatches=z["num_microbatches"],
                       n_chunks=z.get("n_chunks", 1),
                       capacity_factor=m["capacity_factor"])
    program = make_train_program(
        cfg, mesh, run_cfg, ShapeConfig("bench", "train", mix["seq"],
                                        mix["batch"]),
        opt_cfg=opt.OptimizerConfig(**mix["optimizer"]), zcfg=zcfg)
    with mesh:
        init = jax.jit(lambda key: cast_tree(split_params(stack.init_model(
            key, cfg))[0], run_cfg.policy.param_dtype),
            out_shardings=program.param_shardings)
    shardings = {k: program.batch_shardings[k] for k in ("tokens", "targets")}
    return types.SimpleNamespace(program=program, mesh=mesh, init=init,
                                 shardings=shardings, spans=spans, cfg=cfg,
                                 batch=mix["batch"], seq=mix["seq"])


def first_steps(b, seed: int, n_steps: int):
    """Init from the seed, compile, and run the first ``n_steps`` steps.

    Returns the live state for the window and the program's readings:
    losses, first-gradient norms and change norms per leaf, and the first
    gradient itself on the host."""
    import jax
    import jax.numpy as jnp

    from repro.data import DataConfig, DataLoader

    b1 = b.program.opt_cfg.b1
    loader = DataLoader(DataConfig(vocab_size=b.cfg.vocab_size,
                                   seq_len=b.seq, global_batch=b.batch,
                                   seed=seed))

    # The loader's batch for a step as one program, placed as the step
    # takes it: a step's feed is one execution on the chip, not several.
    batch_at = jax.jit(loader.source.batch_at, out_shardings=b.shardings)

    def next_batch():
        with b.spans.span("load"):
            batch = batch_at(np.int32(loader.step))
        loader.step += 1
        return batch

    key = jax.random.PRNGKey(np.uint32(seed))
    with b.mesh:
        params = b.init(key)
        opt_state = b.program.init_opt(params)
    batch = next_batch()
    with b.mesh:
        compiled = b.program.train_step.lower(params, opt_state,
                                              batch).compile()
    grad_norms = jax.jit(lambda mu: jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x))) / (1 - b1), mu))
    diff_norms = jax.jit(lambda a, c: jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - y.astype(jnp.float32)))), a, c))
    batches, losses, g, step_s = [], [], None, []
    for t in range(1, n_steps + 1):
        t0 = time.perf_counter()
        if t > 1:
            batch = next_batch()
        batches.append({k: np.asarray(v) for k, v in batch.items()})
        with b.mesh:
            params, opt_state, met = compiled(params, opt_state, batch)
        losses.append(float(met["loss"]))
        if t > 1:
            step_s.append(time.perf_counter() - t0)
        if t == 1:
            g = harness.flat(grad_norms(opt_state["mu"]))
            first = {k: v / (1 - b1) for k, v in harness.flat_arrays(
                jax.device_get(opt_state["mu"])).items()}
    with b.mesh:
        p0 = b.init(key)
        d = harness.flat(diff_norms(params, p0))
    harness.free(p0)
    state = types.SimpleNamespace(params=params, opt_state=opt_state,
                                  compiled=compiled, next_batch=next_batch,
                                  met=met, batch=batch,
                                  step_s=min(step_s) if step_s else None)
    return state, batches, {"losses": losses, "grad": g, "change": d,
                            "first": first}


def reference_readings(cell: harness.Cell, seed: int, batches,
                       precision: str = "f32", devices=None,
                       drop=None) -> dict:
    """The reference's readings over ``devices``; ``drop``: the experts
    (lo, hi) that add nothing (a fault)."""
    groups, capacity = capacity_groups(cell.config, cell.mix)
    ref = harness.model_module(cell.config, "reference")
    losses, g, d, first = ref.train_readings(
        cell.config, cell.mix["optimizer"], seed, batches, groups, capacity,
        precision=precision, devices=devices, drop=drop)
    return {"losses": losses, "grad": g, "change": d, "first": first}


def worst_leaf_diff(prog: dict, refr: dict) -> tuple:
    """Largest norm of (program - reference) first gradient over leaves,
    each against max(reference leaf norm, median reference leaf norm).
    Returns (gap, leaf)."""
    med = float(np.median(list(refr["grad"].values())))
    worst, where = -1.0, None
    for k, r in refr["first"].items():
        diff = (prog["first"][k] - r).ravel()
        g = float(np.sqrt(np.dot(diff, diff))) / max(refr["grad"][k], med)
        if not np.isfinite(g):
            return float("inf"), k
        if g > worst:
            worst, where = g, k
    return worst, where


def compare(prog: dict, refr: dict) -> dict:
    """The compared numbers, and where the worst leaves are."""
    med_g = float(np.median(list(refr["grad"].values())))
    moved = [k for k, v in refr["grad"].items() if v >= 1e-3 * med_g]
    grad_gap, grad_leaf = harness.worst_leaf_gap(prog["grad"], refr["grad"])
    upd_gap, upd_leaf = harness.worst_leaf_gap(prog["change"],
                                               refr["change"], keep=moved)
    diff, diff_leaf = worst_leaf_diff(prog, refr)
    return {"loss_gap": max(abs(a - c) for a, c
                            in zip(prog["losses"], refr["losses"])),
            "grad_norm_gap": grad_gap, "update_norm_gap": upd_gap,
            "grad_diff": diff,
            "worst": {"grad": grad_leaf, "change": upd_leaf,
                      "grad_diff": diff_leaf},
            "left_out": sorted(set(refr["grad"]) - set(moved))}


def run(cell: harness.Cell, ctx) -> dict:
    import jax

    flops = harness.model_module(cell.config, "flops")
    mix, spans = cell.mix, ctx.spans
    B, S = mix["batch"], mix["seq"]
    b = build(cell, ctx.devices, spans)
    zc = b.program.zcfg
    harness.log(f"train program: mode={zc.mode} num_microbatches="
                f"{zc.num_microbatches} n_chunks={zc.n_chunks} "
                f"batch={B}x{S} mesh={dict(b.mesh.shape)}")
    seed = harness.seed32(ctx.seed)
    st, batches, prog = first_steps(b, seed, mix["check_steps"])
    mem = st.compiled.memory_analysis()
    if mem is not None:
        harness.log(f"train step memory: args={mem.argument_size_in_bytes} "
                    f"temps={mem.temp_size_in_bytes}")
    params, opt_state, compiled = st.params, st.opt_state, st.compiled
    ahead = max(1, math.ceil(AHEAD_S / st.step_s)) if st.step_s else 1
    # The window's batches are made here, so that a step sent in the window
    # is one execution and the runtime's queue holds as many steps as it
    # can; should the window need more, it makes them as it goes.
    n_ready = math.ceil(1.25 * ctx.seconds / st.step_s) + ahead \
        if st.step_s else 0
    ready = collections.deque(st.next_batch() for _ in range(n_ready))
    jax.block_until_ready(list(ready))
    harness.log(f"checked step {st.step_s} s; {ahead} steps kept in flight; "
                f"{n_ready} batches made ahead")
    setup_s = time.perf_counter() - ctx.t_start

    steps, sent, longest = 0, collections.deque(), 0.0
    with harness.Window(ctx.compiles, ctx.trace_dir) as w:
        t_prev = w.t0
        while True:
            batch = ready.popleft() if ready else st.next_batch()
            with spans.span("dispatch"), b.mesh:
                params, opt_state, met = compiled(params, opt_state, batch)
            sent.append(met["loss"])
            steps += 1
            if len(sent) > ahead:
                with spans.span("wait"):
                    sent.popleft().block_until_ready()
            t = time.perf_counter()
            longest, t_prev = max(longest, t - t_prev), t
            if t - w.t0 >= ctx.seconds:
                break
        with spans.span("wait"):
            jax.block_until_ready((params, opt_state, met))
        w.close()
    drain_s = w.t1 - t
    last_loss = float(met["loss"])
    peak = harness.memory_peak(ctx.devices)
    harness.free(params, opt_state, met, batch, st.batch, list(ready))
    del compiled, st
    jax.clear_caches()  # unload the step, whose reservation could linger

    t_ref = time.perf_counter()
    refr = reference_readings(cell, seed, batches, devices=ctx.devices)
    got = compare(prog, refr)
    harness.log(f"reference: {time.perf_counter() - t_ref:.1f} s; losses "
                f"{refr['losses']} against the program's {prog['losses']}; "
                f"worst leaves {got['worst']}; left out of the change: "
                f"{got['left_out'] or 'none'}")
    # Only the numbers whose limits were set from readings of the program
    # and of the control or a fault are compared (PERF.md gives them); the
    # others are logged beside them.
    lim = cell.workload["limits"]
    for k in ("loss_gap", "grad_norm_gap", "update_norm_gap", "grad_diff"):
        if k not in lim:
            harness.log(f"{k} = {got[k]} (not compared)")
    checks = [(k, got[k], lim[k]) for k in sorted(lim)]
    return {
        "setup_s": setup_s,
        "end_to_end": {"train_tokens_per_s": steps * B * S / w.seconds},
        "attempted": steps,
        "failed": 0 if np.isfinite(last_loss) else steps,
        "checks": checks,
        "memory_peak_bytes": peak,
        "window_s": w.seconds,
        "counters": {"steps": steps, "flops": steps * flops.train_step_flops(
            cell.config, B, S)},
        "notes": {"compiles_in_window": ctx.compiles.count,
                  "num_microbatches": zc.num_microbatches,
                  "steps_in_flight": ahead,
                  "longest_host_iteration_s": longest,
                  "drain_s": drain_s, "batches_made_in_window": max(
                      0, steps - n_ready),
                  "last_loss": last_loss},
    }
