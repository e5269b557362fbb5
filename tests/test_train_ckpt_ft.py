"""Training loop, optimizer, checkpointing, data pipeline, fault tolerance."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.core import hardware as HW
from repro.core.planner import plan_zp_group, replan
from repro.core.profiler import ZPGroupShape
from repro.data import DataConfig, DataLoader, write_token_bin
from repro.ft import ElasticController, HeartbeatMonitor, StragglerDetector
from repro.launch.mesh import make_mesh
from repro.models import registry
from repro.models.config import ShapeConfig
from repro.models.modules import Policy, RunConfig
from repro.train import optimizer as opt
from repro.train.loss import chunked_xent_from_hidden, cross_entropy
from repro.train.step import make_train_program

RUN = RunConfig(policy=Policy(compute_dtype=jnp.float32), moe_impl="gather")


# ---------------------------------------------------------------------------
# Optimizer / loss units
# ---------------------------------------------------------------------------

def test_adamw_matches_numpy_reference():
    cfg = opt.OptimizerConfig(peak_lr=1e-2, warmup_steps=0, total_steps=10,
                              weight_decay=0.0, grad_clip=0.0)
    p = {"w": jnp.array([[1.0, -2.0]]), "b": jnp.array([0.5])}
    g = {"w": jnp.array([[0.1, 0.2]]), "b": jnp.array([0.3])}
    st = opt.init_opt_state(p)
    p2, st2, _ = opt.adamw_update(cfg, p, g, st)
    # manual adam step 1: mhat = g, nhat = g^2 -> delta = g/|g| = sign(g)
    lr = float(opt.lr_schedule(cfg, 1))
    want = np.array([[1.0, -2.0]]) - lr * np.sign([[0.1, 0.2]])
    np.testing.assert_allclose(p2["w"], want, atol=1e-4)
    assert int(st2["step"]) == 1


def test_grad_clip_bounds_update():
    cfg = opt.OptimizerConfig(grad_clip=1.0, warmup_steps=0)
    p = {"w": jnp.ones((4,))}
    g = {"w": jnp.full((4,), 100.0)}
    _, _, m = opt.adamw_update(cfg, p, g, opt.init_opt_state(p))
    assert float(m["grad_norm"]) == pytest.approx(200.0)


def test_lr_schedule_shape():
    cfg = opt.OptimizerConfig(peak_lr=1.0, warmup_steps=10, total_steps=100,
                              end_lr_frac=0.1)
    assert float(opt.lr_schedule(cfg, 0)) == 0.0
    assert float(opt.lr_schedule(cfg, 10)) == pytest.approx(1.0)
    assert float(opt.lr_schedule(cfg, 100)) == pytest.approx(0.1, abs=1e-6)


def test_chunked_xent_matches_full():
    key = jax.random.PRNGKey(0)
    B, S, d, V = 2, 50, 16, 37
    hidden = jax.random.normal(key, (B, S, d))
    table = jax.random.normal(jax.random.fold_in(key, 1), (V, d))
    targets = jax.random.randint(jax.random.fold_in(key, 2), (B, S), 0, V)
    logits = jnp.einsum("bsd,vd->bsv", hidden, table)
    want, wm = cross_entropy(logits, targets, z_loss_coef=1e-4)
    got, gm = chunked_xent_from_hidden(hidden, table, targets, chunk=16,
                                       z_loss_coef=1e-4)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # grads too
    g1 = jax.grad(lambda h: chunked_xent_from_hidden(h, table, targets,
                                                     chunk=16)[0])(hidden)
    g2 = jax.grad(lambda h: cross_entropy(
        jnp.einsum("bsd,vd->bsv", h, table), targets)[0])(hidden)
    np.testing.assert_allclose(g1, g2, atol=1e-5)


# ---------------------------------------------------------------------------
# End-to-end training (loss decreases)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["mixtral-d2", "llama3.2-3b"])
def test_training_reduces_loss(mesh4, arch):
    cfg = registry.smoke_config(registry.get_config(arch))
    shape = ShapeConfig("t", "train", 64, 4)
    steps = 60
    program = make_train_program(
        cfg, mesh4, RUN, shape,
        opt_cfg=opt.OptimizerConfig(peak_lr=5e-3, warmup_steps=5,
                                    total_steps=steps))
    loader = DataLoader(DataConfig(cfg.vocab_size, 64, 4, seed=3))
    with mesh4:
        params = program.init_params()
        opt_state = program.init_opt(params)
    losses = []
    for _ in range(steps):
        with mesh4:
            params, opt_state, m = program.train_step(params, opt_state,
                                                      next(loader))
        losses.append(float(m["loss"]))
    assert sum(losses[-5:]) / 5 < sum(losses[:5]) / 5 - 0.1, losses


def test_train_cli_compiles_ahead_and_fits_fixed_batch(tmp_path):
    """launch.train at a cut depth: the step is compiled before the loop,
    every step is logged, and a one-batch token file (the same batch every
    step) drives the loss down from about ln(vocab)."""
    from repro.launch import train as train_cli
    batch, seq = 4, 64
    args = train_cli.parse_args([
        "--arch", "mixtral-d2", "--smoke", "--n-layers", "1",
        "--batch", str(batch), "--seq", str(seq), "--steps", "6",
        "--log-every", "1"])
    vocab = registry.smoke_config(registry.get_config(args.arch)).vocab_size
    args.data = write_token_bin(str(tmp_path / "batch.bin"),
                                batch * seq + 1, vocab)
    res = train_cli.run_training(args)
    assert res.cfg.n_layers == 1
    assert [h["step"] for h in res.history] == [1, 2, 3, 4, 5, 6]
    losses = [h["loss"] for h in res.history]
    assert all(np.isfinite(losses))
    assert abs(losses[0] - np.log(vocab)) < 1.0, losses
    assert losses[-1] < losses[0], losses
    assert res.compiled.memory_analysis() is not None


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(monkeypatch, tmp_path, from_env):
    """The entry points' cache follows JAX_COMPILATION_CACHE_DIR (left to
    JAX, nothing set) and is <checkout>/.jax_cache otherwise."""
    from repro.launch import cache
    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        if from_env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert cache.enable_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir is None
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            got = cache.enable_compile_cache()
            assert got == jax.config.jax_compilation_cache_dir
            root = cache.DEFAULT_DIR.parent
            assert got == str(root / ".jax_cache")
            assert (root / "pyproject.toml").is_file()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    params = {"a": jnp.arange(6.0).reshape(2, 3), "b": {"c": jnp.ones(4)}}
    ostate = opt.init_opt_state(params)
    mgr.save(5, params, ostate, extra={"loader": {"step": 5}})
    step, p2, o2, extra = mgr.restore(params, ostate)
    assert step == 5 and extra["loader"]["step"] == 5
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b), params, p2)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b), ostate, o2)


def test_checkpoint_async_and_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    params = {"a": jnp.zeros(3)}
    for s in (1, 2, 3, 4):
        mgr.save(s, params, blocking=False)
    mgr.wait()
    assert mgr.all_steps() == [3, 4]


def test_checkpoint_detects_corruption(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    params = {"a": jnp.ones(8)}
    mgr.save(1, params)
    # corrupt the array file
    path = os.path.join(str(tmp_path), "step_00000001", "arrays.npz")
    np.savez(path, **{"params\x1fa": np.zeros(8, np.float32)})
    with pytest.raises(IOError):
        mgr.restore(params)


def test_checkpoint_crash_mid_save_keeps_previous_step(tmp_path,
                                                       monkeypatch):
    """ACCEPTANCE (atomic publish, DESIGN.md §13): a crash BETWEEN the
    tmp-dir write and the rename leaves the previous checkpoint as the
    latest — the torn step is invisible to ``all_steps``/``restore`` and
    a later save of the same step recovers cleanly over the debris."""
    import repro.checkpoint.manager as mgr_mod
    mgr = CheckpointManager(str(tmp_path))
    params = {"a": jnp.arange(4.0)}
    mgr.save(1, params)

    real_rename = os.rename

    def crash_rename(src, dst):
        if os.path.basename(dst).startswith("step_"):
            raise RuntimeError("power loss mid-publish")
        return real_rename(src, dst)

    monkeypatch.setattr(mgr_mod.os, "rename", crash_rename)
    params2 = {"a": jnp.full(4, 9.0)}
    with pytest.raises(RuntimeError, match="power loss"):
        mgr.save(2, params2)
    # the torn step 2 never published: tmp dir on disk, invisible to reads
    assert os.path.isdir(os.path.join(str(tmp_path), "step_00000002.tmp"))
    assert mgr.all_steps() == [1]
    step, p, _, _ = mgr.restore(params)
    assert step == 1
    np.testing.assert_allclose(p["a"], np.arange(4.0))
    # power back on: the retried save publishes over the stale tmp debris
    monkeypatch.setattr(mgr_mod.os, "rename", real_rename)
    mgr.save(2, params2)
    assert mgr.all_steps() == [1, 2]
    step, p, _, _ = mgr.restore(params2)
    assert step == 2
    np.testing.assert_allclose(p["a"], np.full(4, 9.0))


def test_checkpoint_save_fsyncs_before_publish(tmp_path, monkeypatch):
    """Durability ordering: every file and directory involved in a save
    is fsync'd BEFORE the rename publishes the step (fsync-after-rename
    alone would allow a torn step to surface after a host crash)."""
    import repro.checkpoint.manager as mgr_mod
    order = []
    real_fsync, real_rename = os.fsync, os.rename
    monkeypatch.setattr(mgr_mod.os, "fsync",
                        lambda fd: (order.append("fsync"), real_fsync(fd))[1])
    monkeypatch.setattr(
        mgr_mod.os, "rename",
        lambda s, d: (order.append("rename"), real_rename(s, d))[1])
    CheckpointManager(str(tmp_path)).save(1, {"a": jnp.ones(2)})
    # arrays.npz + MANIFEST + tmp dir before the rename, parent dir after
    assert order.index("rename") >= 3
    assert order[-1] == "fsync" and order.count("rename") == 1


def test_checkpoint_elastic_reshard(tmp_path, mesh8, mesh4):
    """Save under one mesh, restore onto a different mesh (elastic)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mgr = CheckpointManager(str(tmp_path))
    x = jnp.arange(32.0).reshape(8, 4)
    sharded = jax.device_put(x, NamedSharding(mesh8, P("data", "model")))
    mgr.save(1, {"x": sharded})
    new_sh = {"x": NamedSharding(mesh4, P("model", None))}
    _, restored, _, _ = mgr.restore({"x": x}, shardings=new_sh)
    np.testing.assert_allclose(restored["x"], x)
    assert restored["x"].sharding == new_sh["x"]


# ---------------------------------------------------------------------------
# Data pipeline
# ---------------------------------------------------------------------------

def test_data_deterministic_resume():
    cfg = DataConfig(vocab_size=100, seq_len=16, global_batch=4, seed=7)
    l1 = DataLoader(cfg)
    batches = [next(l1) for _ in range(5)]
    l2 = DataLoader(cfg, start_step=3)
    np.testing.assert_array_equal(batches[3]["tokens"],
                                  next(l2)["tokens"])


def test_data_host_sharding_partitions_batch():
    cfg = DataConfig(vocab_size=100, seq_len=8, global_batch=4, seed=1)
    full = DataLoader(cfg).source.batch_at(0)["tokens"]
    assert full.shape == (4, 8)
    h0 = DataLoader(cfg, host_index=0, host_count=2).source.batch_at(0)
    h1 = DataLoader(cfg, host_index=1, host_count=2).source.batch_at(0)
    assert h0["tokens"].shape == (2, 8)
    assert not np.array_equal(h0["tokens"], h1["tokens"])


def test_memmap_source(tmp_path):
    path = str(tmp_path / "toks.bin")
    write_token_bin(path, 10_000, 50_000, seed=0)
    cfg = DataConfig(vocab_size=50_000, seq_len=32, global_batch=2,
                     path=path)
    l = DataLoader(cfg)
    b0 = next(l)
    b1 = next(l)
    assert b0["tokens"].shape == (2, 32)
    np.testing.assert_array_equal(b0["tokens"][:, 1:], b0["targets"][:, :-1])
    assert not np.array_equal(b0["tokens"], b1["tokens"])


# ---------------------------------------------------------------------------
# Fault tolerance
# ---------------------------------------------------------------------------

def test_heartbeat_detects_dead_host():
    clock = {"t": 0.0}
    mon = HeartbeatMonitor(["a", "b"], clock=lambda: clock["t"])
    clock["t"] = 20.0
    mon.beat("a")
    clock["t"] = 35.0
    assert mon.dead_hosts() == ["b"]


def test_straggler_detector_flags_slow_group():
    det = StragglerDetector(["attn", "exp"], z_thresh=3.0, patience=2)
    for _ in range(10):
        det.record("attn", 1.0)
        det.record("exp", 1.0)
    assert det.stragglers() == []
    for _ in range(6):
        det.record("exp", 3.0)
        det.stragglers()
    assert "exp" in det.stragglers()
    assert det.slow_factor("exp") > 2.0


def test_elastic_controller_shrinks_and_replans():
    cfg = registry.get_config("mixtral-d1")
    zp = ZPGroupShape(M=4, N=4, attn_class=HW.A40, exp_class=HW.V100)
    plan = plan_zp_group(cfg, zp, global_batch=16, seq_len=4096)
    ctl = ElasticController(cfg, plan, 16, 4096,
                            attn_hosts=["a0", "a1", "a2", "a3"],
                            exp_hosts=["e0", "e1", "e2", "e3"])
    # kill one attention host and one expert host
    ctl.heartbeat.last_seen["a3"] -= 1e6
    ctl.heartbeat.last_seen["e3"] -= 1e6
    ev = ctl.tick()
    assert ev.kind == "shrink"
    assert ev.plan.zp.M == 3 and ev.plan.zp.N == 3


def test_straggler_replan_increases_offload():
    cfg = registry.get_config("mixtral-d1")
    zp = ZPGroupShape(M=4, N=4, attn_class=HW.A40, exp_class=HW.V100)
    plan = plan_zp_group(cfg, zp, global_batch=16, seq_len=4096)
    slowed = replan(cfg, plan, 16, 4096, slow_factor=2.0)
    # a 2x slower expert class must shift at least as much work across
    assert sum(slowed.offload) >= sum(plan.offload)
    assert slowed.predicted.iter_time >= plan.predicted.iter_time


def test_replan_raises_when_group_not_viable():
    cfg = registry.get_config("mixtral-d1")
    zp = ZPGroupShape(M=1, N=1, attn_class=HW.A40, exp_class=HW.V100)
    plan = plan_zp_group(cfg, zp, global_batch=16, seq_len=4096)
    with pytest.raises(RuntimeError):
        replan(cfg, plan, 16, 4096, lost_exp=1)
