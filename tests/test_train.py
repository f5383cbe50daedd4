"""Training substrate: optimizers, microbatching, checkpoint/restore,
elastic resharding, gradient compression, straggler watchdog, e2e driver."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, replace
from repro.configs.base import LMConfig
from repro.launch.mesh import make_mesh
from repro.launch.train import train
from repro.train import (
    StragglerWatchdog,
    checkpoint,
    compressed_psum,
    init_residual,
    make_optimizer,
    make_train_step,
    plan_mesh,
    simulate_failure,
)
from repro.train.optimizer import adafactor, adamw, global_norm


def _quad_loss(params, batch):
    pred = batch["x"] @ params["w"] + params["b"]
    loss = jnp.mean((pred - batch["y"]) ** 2)
    return loss, {"loss": loss}


def _toy(seed=0, n=64, d=8):
    rng = np.random.default_rng(seed)
    w_true = rng.standard_normal((d, 1)).astype(np.float32)
    x = rng.standard_normal((n, d)).astype(np.float32)
    y = x @ w_true + 0.01 * rng.standard_normal((n, 1)).astype(np.float32)
    params = {"w": jnp.zeros((d, 1)), "b": jnp.zeros((1,))}
    return params, {"x": jnp.asarray(x), "y": jnp.asarray(y)}


class TestOptimizers:
    @pytest.mark.parametrize("name", ["adamw", "adafactor", "sgdm"])
    def test_reduces_loss(self, name):
        cfg = replace(get_config("gin-tu"), optimizer=name, learning_rate=0.05,
                      weight_decay=0.0, warmup_steps=1, grad_clip=0.0)
        opt = make_optimizer(cfg)
        params, batch = _toy()
        state = opt.init(params)
        step = jax.jit(make_train_step(cfg, _quad_loss, opt))
        l0 = float(_quad_loss(params, batch)[0])
        for _ in range(60):
            params, state, m = step(params, state, batch)
        assert float(m["loss"]) < 0.5 * l0

    def test_grad_clip(self):
        cfg = replace(get_config("gin-tu"), grad_clip=1e-6)
        opt = make_optimizer(cfg)
        params, batch = _toy()
        p2, _, m = jax.jit(make_train_step(cfg, _quad_loss, opt))(
            params, opt.init(params), batch)
        # with a microscopic clip, params barely move
        assert float(global_norm(jax.tree.map(
            lambda a, b: a - b, p2, params))) < 1e-3

    def test_adafactor_state_is_factored(self):
        cfg = replace(get_config("kimi-k2-1t-a32b"), optimizer="adafactor")
        opt = adafactor(cfg)
        params = {"w": jnp.zeros((64, 32)), "b": jnp.zeros((64,))}
        st = opt.init(params)
        assert st["vr"]["w"].shape == (64,)     # row stats
        assert st["vc"]["w"].shape == (32,)     # col stats
        assert st["vr"]["b"].shape == (64,)     # unfactored 1-D

    def test_adamw_moment_dtype(self):
        cfg = replace(get_config("gin-tu"), moment_dtype="bfloat16")
        opt = adamw(cfg)
        st = opt.init({"w": jnp.zeros((4, 4))})
        assert st["m"]["w"].dtype == jnp.bfloat16

    def test_microbatched_equals_full_batch(self):
        """Grad accumulation over n microbatches == single big batch."""
        cfg1 = replace(get_config("gin-tu"), microbatches=1, grad_clip=0.0,
                       learning_rate=0.1, warmup_steps=1, weight_decay=0.0)
        cfg4 = replace(cfg1, microbatches=4)
        opt1, opt4 = make_optimizer(cfg1), make_optimizer(cfg4)
        params, batch = _toy(n=64)
        s1 = jax.jit(make_train_step(cfg1, _quad_loss, opt1))
        s4 = jax.jit(make_train_step(cfg4, _quad_loss, opt4))
        p1, _, _ = s1(params, opt1.init(params), batch)
        p4, _, _ = s4(params, opt4.init(params), batch)
        np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p4["w"]),
                                   rtol=1e-5, atol=1e-6)


class TestCheckpoint:
    def test_save_restore_roundtrip(self, tmp_path):
        tree = {"a": jnp.arange(8, dtype=jnp.float32),
                "b": {"c": jnp.ones((3, 4), jnp.bfloat16)},
                "count": jnp.int32(7)}
        checkpoint.save(str(tmp_path), 5, tree)
        restored, step = checkpoint.restore(str(tmp_path), tree)
        assert step == 5
        np.testing.assert_array_equal(np.asarray(restored["a"]),
                                      np.asarray(tree["a"]))
        assert restored["b"]["c"].dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(restored["b"]["c"], np.float32),
            np.asarray(tree["b"]["c"], np.float32))

    def test_keep_last_n(self, tmp_path):
        tree = {"x": jnp.zeros(2)}
        for s in range(6):
            checkpoint.save(str(tmp_path), s, tree, keep=3)
        assert checkpoint.all_steps(str(tmp_path)) == [3, 4, 5]

    def test_async_save(self, tmp_path):
        tree = {"x": jnp.arange(4.0)}
        t = checkpoint.save(str(tmp_path), 1, tree, blocking=False)
        t.join()
        assert checkpoint.latest_step(str(tmp_path)) == 1

    def test_atomic_commit_no_tmp_left(self, tmp_path):
        checkpoint.save(str(tmp_path), 3, {"x": jnp.zeros(2)})
        assert not [d for d in os.listdir(tmp_path) if d.endswith(".tmp")]

    def test_restore_with_shardings(self, tmp_path):
        mesh = make_mesh((1,), ("data",))
        from jax.sharding import NamedSharding, PartitionSpec as P
        tree = {"w": jnp.arange(16, dtype=jnp.float32).reshape(4, 4)}
        checkpoint.save(str(tmp_path), 1, tree)
        sh = {"w": NamedSharding(mesh, P("data", None))}
        restored, _ = checkpoint.restore(str(tmp_path), tree, shardings=sh)
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(tree["w"]))
        assert restored["w"].sharding == sh["w"]


class TestElastic:
    def test_plan_keeps_tp_on_failure(self):
        before, after = simulate_failure(512, 16, model_parallel=16,
                                         multi_pod=True)
        assert before.shape == (2, 16, 16)
        assert after.shape[-1] == 16            # TP degree preserved
        assert after.n_devices <= 512 - 16

    def test_plan_degrades_tp_when_starved(self):
        plan = plan_mesh(8, model_parallel=16)
        assert plan.shape[-1] <= 8

    def test_restore_onto_smaller_mesh(self, tmp_path):
        """Checkpoint written under one layout restores under another —
        the reshard-on-restore contract (elastic downscale)."""
        tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        checkpoint.save(str(tmp_path), 2, tree)
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = make_mesh((1,), ("data",))
        restored, _ = checkpoint.restore(
            str(tmp_path), tree,
            shardings={"w": NamedSharding(mesh, P(None, None))})
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(tree["w"]))


class TestCompression:
    def test_compressed_psum_single_shard_exact_feedback(self):
        """n=1 shard: quantisation error is carried in the residual, so two
        steps of the same gradient reconstruct it to within int8 precision."""
        mesh = make_mesh((1,), ("data",))
        from jax.sharding import PartitionSpec as P

        g = {"w": jnp.asarray(np.random.default_rng(0).standard_normal(64),
                              jnp.float32)}
        r = init_residual(g)

        def f(g, r):
            return compressed_psum(g, r, ("data",), 1)

        out, res = jax.shard_map(f, mesh=mesh, in_specs=(P(), P()),
                                 out_specs=(P(), P()), check_vma=False)(g, r)
        err1 = np.abs(np.asarray(out["w"]) - np.asarray(g["w"])).max()
        scale = np.abs(np.asarray(g["w"])).max() / 127
        assert err1 <= scale + 1e-6
        # residual + quantised == original (error feedback invariant)
        np.testing.assert_allclose(np.asarray(out["w"]) + np.asarray(res["w"]),
                                   np.asarray(g["w"]), rtol=1e-5, atol=1e-6)


class TestStraggler:
    def test_flags_slow_step(self):
        calls = []
        dog = StragglerWatchdog(threshold=2.0, min_samples=3,
                                backup_dispatch=calls.append)
        for s in range(10):
            dog.observe(s, 0.1)
        ev = dog.observe(10, 0.5)
        assert ev is not None and ev.ratio == pytest.approx(5.0)
        assert calls == [10]

    def test_no_flag_within_threshold(self):
        dog = StragglerWatchdog(threshold=3.0, min_samples=3)
        for s in range(10):
            assert dog.observe(s, 0.1 + 0.01 * (s % 2)) is None


class TestEndToEnd:
    def test_train_resume_continues(self, tmp_path):
        out1 = train("gin-tu", steps=6, ckpt_dir=str(tmp_path), ckpt_every=3,
                     log_every=100, async_ckpt=False)
        assert np.isfinite(out1["loss"])
        # resume from step 6 checkpoint and continue to 8
        out2 = train("gin-tu", steps=8, ckpt_dir=str(tmp_path), ckpt_every=3,
                     log_every=100, async_ckpt=False)
        assert np.isfinite(out2["loss"])

    def test_train_lm_reduced(self):
        out = train("deepseek-v2-lite-16b", steps=3, batch=4, seq=16,
                    log_every=100)
        assert np.isfinite(out["loss"])
