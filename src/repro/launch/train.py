"""End-to-end training driver.

Runs real training on whatever devices exist (CPU-scale smoke through
full-pod) with checkpointing, resume, fault-tolerance hooks and zebra
parallelism for MoE archs.

    PYTHONPATH=src python -m repro.launch.train --arch mixtral-d2 \
        --steps 50 --batch 8 --seq 256 --mesh 1x2 --smoke

--smoke uses the reduced same-family config (registry.smoke_config) so a
~CPU-sized model trains a few hundred steps; omit it to use the full config
(real hardware). --n-layers cuts depth and keeps the published widths.

The step is compiled ahead of the loop, so its compile time, its device
memory (``memory_analysis``) and its HLO are known before the first step.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.core.zebra_spmd import ZebraConfig
from repro.obs import format_report, write_chrome_trace
from repro.obs import trace as obs_trace
from repro.data import DataConfig, DataLoader
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import registry
from repro.models.config import ShapeConfig
from repro.models.modules import Policy, RunConfig
from repro.train import optimizer as opt
from repro.train.step import make_train_program


@dataclasses.dataclass
class TrainRun:
    """What ``run_training`` returns: the config, the compiled step, the
    final parameters and one record per logged step."""
    cfg: object
    compiled: object
    params: object
    history: list


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-d2")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--mesh", default="1x1", help="DATAxMODEL, e.g. 2x4")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--n-layers", type=int, default=None,
                    help="cut depth to this many layers (widths unchanged)")
    ap.add_argument("--gmm-kernel", action="store_true",
                    help="run every expert FFN on the Pallas grouped-GEMM "
                         "kernels (default: each MoE path's own choice)")
    ap.add_argument("--zebra", action="store_true", default=True)
    ap.add_argument("--no-zebra", dest="zebra", action="store_false")
    ap.add_argument("--zebra-mode", default="replicated")
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--n-chunks", type=int, default=1,
                    help="capacity chunks for overlapped dispatch "
                         "(alltoall mode, DESIGN.md §8)")
    ap.add_argument("--offload-experts", type=int, default=0,
                    help="experts kept replicated attention-side "
                         "(alltoall mode Asym-EA offload)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--data", default=None, help="token .bin (else synthetic)")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome/Perfetto trace of the run "
                         "(obs §15; one tick per training step)")
    ap.add_argument("--trace-wall", action="store_true",
                    help="trace with wall-clock timestamps instead of the "
                         "deterministic step clock")
    return ap.parse_args(argv)


def main(argv=None):
    enable_compile_cache()
    run_training(parse_args(argv))
    return 0


def run_training(args) -> TrainRun:
    cfg = registry.get_config(args.arch)
    if args.smoke:
        cfg = registry.smoke_config(cfg)
    if args.n_layers:
        cfg = dataclasses.replace(cfg, n_layers=args.n_layers)
    d, m = (int(x) for x in args.mesh.split("x"))
    mesh = make_mesh((d, m), ("data", "model"))
    run = RunConfig(policy=Policy(), attn_impl="chunked", moe_impl="gather",
                    remat="full", use_gmm_kernel=args.gmm_kernel)
    shape = ShapeConfig("cli", "train", args.seq, args.batch)
    zcfg = None
    if args.zebra and cfg.is_moe:
        zcfg = ZebraConfig(mode=args.zebra_mode,
                           num_microbatches=args.microbatches,
                           n_chunks=args.n_chunks,
                           offload_experts=args.offload_experts)
    opt_cfg = opt.OptimizerConfig(peak_lr=args.lr, warmup_steps=20,
                                  total_steps=args.steps)
    program = make_train_program(cfg, mesh, run, shape, opt_cfg=opt_cfg,
                                 zcfg=zcfg)

    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                          global_batch=args.batch, path=args.data)
    loader = DataLoader(data_cfg)

    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    start_step = 0
    with mesh:
        params = program.init_params(seed=0)
        opt_state = program.init_opt(params)
    if ckpt and args.resume and ckpt.latest_step() is not None:
        start_step, params, opt_state, extra = ckpt.restore(
            jax.tree.map(lambda x: x, params), opt_state,
            shardings=program.param_shardings,
            opt_shardings=program.opt_shardings)
        loader.load_state_dict(extra.get("loader", {"step": start_step}))
        print(f"[train] resumed from step {start_step}")
    loader.step = max(loader.step, start_step)

    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"[train] arch={cfg.name} params={n_params/1e6:.1f}M "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"zebra={dataclasses.asdict(program.zcfg) if program.zcfg else None}")

    def batch_at_step():
        batch = next(loader)
        # modality-frontend stubs
        if cfg.is_encdec:
            batch["encoder_embeds"] = jnp.zeros(
                (args.batch, cfg.encoder_seq, cfg.d_model),
                run.policy.compute_dtype)
        if cfg.vision_seq > 0:
            batch["vision_embeds"] = jnp.zeros(
                (args.batch, cfg.vision_seq, cfg.vision_dim or cfg.d_model),
                run.policy.compute_dtype)
        return jax.device_put(batch, {k: program.batch_shardings[k]
                                      for k in batch})

    batch = batch_at_step()
    t0 = time.perf_counter()
    with mesh:
        compiled = program.train_step.lower(params, opt_state, batch).compile()
    mem = compiled.memory_analysis()
    mem_txt = "" if mem is None else (
        f" args={mem.argument_size_in_bytes / 2**30:.2f}GiB"
        f" temps={mem.temp_size_in_bytes / 2**30:.2f}GiB"
        f" outputs={mem.output_size_in_bytes / 2**30:.2f}GiB"
        f" aliased={mem.alias_size_in_bytes / 2**30:.2f}GiB")
    print(f"[train] compiled step in {time.perf_counter() - t0:.1f}s"
          f"{mem_txt}", flush=True)

    tracer = None
    last_logged: dict = {}
    if args.trace_out:
        tracer = obs_trace.Tracer(wall=bool(args.trace_wall))
        obs_trace.install(tracer)
        tracer.declare_track("train", pid="train")
        tracer.registry.register("train", lambda: dict(last_logged))

    history = []
    t_log, step_log = time.perf_counter(), start_step
    for step in range(start_step, args.steps):
        if tracer is not None:
            tracer.advance(step)
        if step > start_step:
            batch = batch_at_step()
        with mesh, obs_trace.TRACER.span("train", "step", step=step):
            params, opt_state, metrics = compiled(params, opt_state, batch)
        if (step + 1) % args.log_every == 0 or step == start_step:
            rec = {k: float(metrics[k])
                   for k in ("loss", "nll", "grad_norm", "lr")}
            # float() waited for the step: the window since the last log
            # holds finished steps only.
            now = time.perf_counter()
            rec.update(step=step + 1,
                       ms_per_step=(now - t_log) * 1e3 / (step + 1 - step_log))
            t_log, step_log = now, step + 1
            history.append(rec)
            print(f"step {step + 1:5d} loss={rec['loss']:.4f} "
                  f"nll={rec['nll']:.4f} gnorm={rec['grad_norm']:.3f} "
                  f"lr={rec['lr']:.2e} {rec['ms_per_step']:.1f} ms/step",
                  flush=True)
            if tracer is not None:
                last_logged.update(step=step + 1, loss=rec["loss"],
                                   nll=rec["nll"],
                                   ms_per_step=round(rec["ms_per_step"], 1))
                tracer.count("train", "loss", rec["loss"])
        if ckpt and (step + 1) % args.ckpt_every == 0:
            ckpt.save(step + 1, params, opt_state,
                      extra={"loader": loader.state_dict()}, blocking=False)
    if ckpt:
        ckpt.save(args.steps, params, opt_state,
                  extra={"loader": loader.state_dict()})
        ckpt.wait()
    if tracer is not None:
        obj = write_chrome_trace(tracer, args.trace_out)
        obs_trace.install(None)
        print(f"[train] trace: {len(obj['traceEvents'])} events "
              f"-> {args.trace_out}")
        for line in format_report(obj["reproIdle"]).splitlines():
            print(f"[train] idle: {line}")
    print(f"[train] done: final loss {float(metrics['loss']):.4f}")
    return TrainRun(cfg=cfg, compiled=compiled, params=params,
                    history=history)


if __name__ == "__main__":
    sys.exit(main())
