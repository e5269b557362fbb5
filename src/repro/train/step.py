"""Jitted training / serving step builders with full sharding plumbing.

`TrainProgram` is the single object the launcher and the tests share:
abstract param/opt shapes, NamedShardings derived from logical axes, and
the jitted step functions.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import zebra_spmd
from repro.models import stack
from repro.models.config import ModelConfig, ShapeConfig
from repro.models.modules import RunConfig
from repro.obs import trace as obs_trace
from repro.pytree import split_params, tree_map_with_path_names
from repro.sharding.rules import ShardingRules, rules_for, specs_for
from repro.train import optimizer as opt
from repro.train.loss import total_loss


def fit_batch_axes(batch: int, mesh: Mesh, axes: tuple) -> tuple:
    """Largest prefix of `axes` whose product divides `batch`."""
    out = []
    prod = 1
    for a in axes:
        prod *= mesh.shape[a]
        if batch % prod == 0:
            out.append(a)
        else:
            break
    return tuple(out)


@dataclasses.dataclass
class TrainProgram:
    cfg: ModelConfig
    run: RunConfig
    mesh: Mesh
    rules: ShardingRules
    opt_cfg: opt.OptimizerConfig
    zcfg: Optional[zebra_spmd.ZebraConfig]
    param_shapes: object
    param_shardings: object
    opt_shardings: object
    batch_shardings: object
    train_step: Callable  # (params, opt_state, batch) -> (params, opt, metrics)
    loss_fn: Callable

    def init_params(self, seed: int = 0):
        """Materialize sharded params on the mesh."""
        init = functools.partial(self._init_values, seed)
        with self.mesh:
            return jax.jit(init, out_shardings=self.param_shardings)()

    def _init_values(self, seed):
        from repro.pytree import cast_tree
        vals = split_params(
            stack.init_model(jax.random.PRNGKey(seed), self.cfg))[0]
        return cast_tree(vals, self.run.policy.param_dtype)

    @property
    def master_weights(self) -> bool:
        import jax.numpy as jnp
        return jnp.dtype(self.run.policy.param_dtype) != jnp.float32

    def init_opt(self, params):
        with self.mesh:
            return jax.jit(
                functools.partial(opt.init_opt_state,
                                  master_weights=self.master_weights),
                out_shardings=self.opt_shardings)(params)


def _zero1_rules(rules: ShardingRules) -> ShardingRules:
    r = dict(rules.rules)
    r["zero"] = "data"
    return dataclasses.replace(rules, rules=r)


def make_train_program(cfg: ModelConfig, mesh: Mesh, run: RunConfig,
                       shape: ShapeConfig,
                       opt_cfg: Optional[opt.OptimizerConfig] = None,
                       zcfg: Optional[zebra_spmd.ZebraConfig] = None,
                       donate: bool = True,
                       constrain_grads: bool = False,
                       accum_steps: int = 1) -> TrainProgram:
    opt_cfg = opt_cfg or opt.OptimizerConfig()
    if cfg.is_moe:
        variant = "hybrid" if (zcfg is not None
                               and zcfg.mode == "replicated") else "ep"
    else:
        variant = "default"
    rules = rules_for(cfg, mesh, variant=variant)
    if zcfg is not None:
        zb = fit_batch_axes(shape.global_batch, mesh, rules.batch_axes)
        nsh = 1
        for a in zb:
            nsh *= mesh.shape[a]
        R = zcfg.num_microbatches
        B = shape.global_batch
        while R > 1 and (B % R or (B // R) % nsh):
            R -= 1  # microbatches must keep the batch shardable
        zcfg = dataclasses.replace(zcfg, batch_axes=zb, num_microbatches=R)
        if cfg.is_moe and zcfg.mode == "alltoall":
            # Chunked-dispatch knobs: the remote expert count must divide
            # over the EP axis after Asym-EA offload; shrink the offload
            # until it does rather than failing inside the engine.
            n_ep = mesh.shape[zcfg.ep_axis]
            off = max(min(zcfg.offload_experts, cfg.n_experts - n_ep), 0)
            while off and (cfg.n_experts - off) % n_ep:
                off -= 1
            zcfg = dataclasses.replace(zcfg, offload_experts=off,
                                       n_chunks=max(int(zcfg.n_chunks), 1))

    # Abstract shapes + shardings ------------------------------------------------
    from repro.pytree import cast_tree
    from repro.sharding.rules import fitted_shardings
    pshapes, paxes = abstract_params(cfg)
    pshapes = jax.eval_shape(lambda t: cast_tree(t, run.policy.param_dtype),
                             pshapes)
    psh = fitted_shardings(pshapes, paxes, rules, mesh)
    master = jnp.dtype(run.policy.param_dtype) != jnp.float32
    oshapes = jax.eval_shape(
        lambda t: opt.init_opt_state(t, master_weights=master), pshapes)
    o_axes = opt.opt_state_axes(paxes, master_weights=master)
    osh = fitted_shardings(oshapes, o_axes, _zero1_rules(rules), mesh)

    baxes = fit_batch_axes(shape.global_batch, mesh, rules.batch_axes)
    bsh = NamedSharding(mesh, P(baxes))

    from repro.sharding.rules import make_constrainer
    act_rules = dataclasses.replace(rules, batch_axes=baxes)
    run = dataclasses.replace(run, constrain=make_constrainer(act_rules, mesh))

    override = None
    if zcfg is not None and cfg.is_moe:
        override = zebra_spmd.make_layer_override(mesh, cfg, run, zcfg)

    def loss_fn(params, batch):
        hidden, _, aux = stack.apply_model(
            params, cfg, run, batch["tokens"],
            encoder_embeds=batch.get("encoder_embeds"),
            vision_embeds=batch.get("vision_embeds"),
            layer_override=override, return_hidden=True)
        table = params.get("lm_head", params["embed"]["table"])
        from repro.train.loss import chunked_xent_from_hidden
        with obs_trace.scope("head"):
            loss, metrics = chunked_xent_from_hidden(
                hidden, table.astype(run.policy.compute_dtype),
                batch["targets"], unroll=cfg.unroll, constrain=run.constrain)
        loss = loss + aux.get("moe_aux_loss", 0.0) + aux.get("moe_z_loss", 0.0)
        metrics = dict(metrics, **aux, loss=loss)
        return loss, metrics

    def train_step(params, opt_state, batch):
        if accum_steps > 1:
            # Gradient accumulation: scan over batch slices, mean grads.
            B = shape.global_batch
            assert B % accum_steps == 0

            def slice_batch(b, i):
                return jax.tree.map(
                    lambda x: jax.lax.dynamic_slice_in_dim(
                        x, i * (x.shape[0] // accum_steps),
                        x.shape[0] // accum_steps, axis=0), b)

            def accum_body(carry, i):
                g_acc, l_acc = carry
                (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, slice_batch(batch, i))
                return (jax.tree.map(jnp.add, g_acc, g), l_acc + l), m

            zero_g = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (g_sum, l_sum), ms = jax.lax.scan(
                accum_body, (zero_g, jnp.zeros((), jnp.float32)),
                jnp.arange(accum_steps))
            grads = jax.tree.map(lambda g: g / accum_steps, g_sum)
            loss = l_sum / accum_steps
            metrics = jax.tree.map(lambda m: jnp.mean(m, axis=0), ms)
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        if constrain_grads:
            # Pin gradient shardings to the param layout BEFORE the
            # optimizer: turns XLA's full-size gradient all-reduce into
            # reduce-scatter (+ sharded elementwise update).
            grads = jax.lax.with_sharding_constraint(grads, psh)
        with obs_trace.scope("optimizer"):
            params, opt_state, om = opt.adamw_update(opt_cfg, params, grads,
                                                     opt_state)
        metrics.update(om)
        return params, opt_state, metrics

    from repro.configs.inputs import input_specs
    front_sh = NamedSharding(mesh, P(baxes, None, None))
    batch_shardings = {
        k: (bsh if k in ("tokens", "targets") else front_sh)
        for k in input_specs(cfg, shape)
    }

    jit_step = jax.jit(
        train_step,
        in_shardings=(psh, osh, batch_shardings),
        out_shardings=(psh, osh, None),
        donate_argnums=(0, 1) if donate else (),
    )

    return TrainProgram(cfg=cfg, run=run, mesh=mesh, rules=rules,
                        opt_cfg=opt_cfg, zcfg=zcfg, param_shapes=pshapes,
                        param_shardings=psh, opt_shardings=osh,
                        batch_shardings=batch_shardings,
                        train_step=jit_step, loss_fn=loss_fn)


def abstract_params(cfg: ModelConfig):
    """(ShapeDtypeStruct value tree, logical-axes tree) without allocating.

    Axes are static Python data produced during tracing, so they are
    captured through a side channel while eval_shape abstracts the values.
    """
    box = {}

    def split_build():
        vals, axes = split_params(
            stack.init_model(jax.random.PRNGKey(0), cfg))
        box["axes"] = axes
        return vals

    vals = jax.eval_shape(split_build)
    return vals, box["axes"]
