"""Training driver: the compiled zebra train step of ``launch.train``, fed
by ``repro.data.DataLoader`` one batch per step.

Set-up builds one compiled step with its state and drives it from the
seed through ``check_steps`` steps on distinct batches; those steps are
what the reference replays. The same step and state then run the window:
each step loads and places its batch, is dispatched, and waits for the
step before it, so one step is in flight. The window ends when the last
step's outputs are ready.

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

import time
import types

import numpy as np

import harness
from reference import mixtral as ref


def capacity_groups(config: dict, mix: dict) -> tuple:
    """(groups, capacity) of the job's expert capacity, as the zebra
    engine states it: a group is one microbatch on one batch shard, and
    each expert takes round_up(int(T * k / E * capacity_factor), 8) of a
    group's T tokens."""
    groups = mix["zebra"]["num_microbatches"] * mix["mesh"][0]
    T = mix["batch"] * mix["seq"] // groups
    C = int(T * config["top_k"] / config["n_experts"]
            * config["capacity_factor"])
    return groups, max(-(-C // 8) * 8, 8)


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

    def next_batch():
        with b.spans.span("load"):
            return jax.device_put(next(loader), b.shardings)

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
    batches, losses, g = [], [], None
    for t in range(1, n_steps + 1):
        if t > 1:
            batch = next_batch()
        batches.append({k: np.asarray(v) for k, v in batch.items()})
        with b.mesh:
            params, opt_state, met = compiled(params, opt_state, batch)
        losses.append(float(met["loss"]))
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
                                  met=met, batch=batch)
    return state, batches, {"losses": losses, "grad": g, "change": d,
                            "first": first}


def reference_readings(cell: harness.Cell, seed: int, batches,
                       precision: str = "f32") -> dict:
    groups, capacity = capacity_groups(cell.config, cell.mix)
    losses, g, d, first = ref.train_readings(
        cell.config, cell.mix["optimizer"], seed, batches, groups, capacity,
        precision=precision)
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

    from flops import train_step_flops

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
    setup_s = time.perf_counter() - ctx.t_start

    params, opt_state, compiled = st.params, st.opt_state, st.compiled
    steps, prev = 0, None
    with harness.Window(ctx.compiles, ctx.trace_dir) as w:
        while True:
            batch = st.next_batch()
            with spans.span("dispatch"), b.mesh:
                params, opt_state, met = compiled(params, opt_state, batch)
            if prev is not None:
                with spans.span("wait"):
                    prev.block_until_ready()
            prev = met["loss"]
            steps += 1
            if time.perf_counter() - w.t0 >= ctx.seconds:
                break
        with spans.span("wait"):
            jax.block_until_ready((params, opt_state, met))
        w.close()
    last_loss = float(met["loss"])
    peak = harness.memory_peak(ctx.devices)
    harness.free(params, opt_state, met, batch, st.batch)
    del compiled, st
    jax.clear_caches()  # unload the step, whose reservation could linger

    t_ref = time.perf_counter()
    refr = reference_readings(cell, seed, batches)
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
        "counters": {"steps": steps,
                     "flops": steps * train_step_flops(cell.config, B, S)},
        "notes": {"compiles_in_window": ctx.compiles.count,
                  "num_microbatches": zc.num_microbatches,
                  "last_loss": last_loss},
    }
