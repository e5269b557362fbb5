"""A run whose timed path is broken underneath comes out not correct:
the harness runs on the CPU at a tiny size with one fault planted in the
program, once for each fault a cell of that driver can have."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from conftest import run_cell


def _broken_train_step(monkeypatch, fault):
    import repro.train.step as step_mod
    make = step_mod.make_train_program

    def patched(*a, **kw):
        prog = make(*a, **kw)
        orig = prog.train_step

        def unchanged(p, o, batch):
            _, _, met = orig(p, o, batch)
            return p, o, met

        def half_batch(p, o, batch):
            # The first half of the rows twice: the mean is over them alone,
            # at the shapes (and batch shards) the step was built for.
            n = batch["tokens"].shape[0] // 2
            return orig(p, o, {k: jnp.concatenate([v[:n], v[:n]])
                               for k, v in batch.items()})

        fn = {"unchanged": unchanged, "half_batch": half_batch}[fault]
        return dataclasses.replace(prog, train_step=jax.jit(fn))

    monkeypatch.setattr(step_mod, "make_train_program", patched)


@pytest.mark.parametrize("cell", ["tiny-train", "tiny-a2a"])
@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_broken_train_step_is_not_correct(checkout, capsys, monkeypatch,
                                          fault, cell):
    _broken_train_step(monkeypatch, fault)
    res = run_cell(checkout, capsys, cell)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("fault", ["dropped_shard", "no_exchange"])
def test_broken_exchange_is_not_correct(checkout, capsys, monkeypatch,
                                        fault):
    """The expert-parallel cell on four devices: one chip's experts add
    nothing (``dropped_shard``), or the all-to-alls move nothing, so each
    chip's tokens meet the wrong experts (``no_exchange``)."""
    if fault == "dropped_shard":
        import repro.kernels.ops as kops
        orig = kops.moe_ffn_packed

        def dropped(buf, *a, **kw):
            out = orig(buf, *a, **kw)
            last = jax.lax.axis_index("model") == jax.lax.psum(1, "model") - 1
            return jnp.where(last, jnp.zeros_like(out), out)
        monkeypatch.setattr(kops, "moe_ffn_packed", dropped)
    else:
        monkeypatch.setattr(jax.lax, "all_to_all",
                            lambda x, *a, **kw: x)
    res = run_cell(checkout, capsys, "tiny-a2a")
    assert res["correct"] is False, res["checks"]


def test_altered_served_token_is_not_correct(checkout, capsys, monkeypatch):
    import repro.serve.sampling as sampling
    orig = sampling.sample_tokens

    def altered(logits, *a, **kw):
        return (orig(logits, *a, **kw) + 1) % logits.shape[-1]

    monkeypatch.setattr(sampling, "sample_tokens", altered)
    res = run_cell(checkout, capsys, "tiny-serve", seconds=2.0)
    assert res["correct"] is False, res["checks"]


def test_sound_runs_are_correct(checkout, capsys):
    for w, s in (("tiny-train", 1.0), ("tiny-a2a", 1.0), ("tiny-serve", 2.0)):
        res = run_cell(checkout, capsys, w, seed=2**31 + 3, seconds=s)
        assert res["correct"] is True, (w, res["checks"])
