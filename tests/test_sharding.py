"""GroupSharded / ZeRO tests on the virtual 8-device mesh.

Reference behavior being checked (fleet/meta_parallel/sharding/*):
stage 1 shards optimizer states, stage 2 also re-lays gradients, stage 3
also shards parameters — while training math stays identical to plain DP.
"""
import numpy as np
import pytest

import jax

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as opt


def _make_model(seed=7):
    paddle.seed(seed)
    return nn.Sequential(
        nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 8)
    )


def _train_steps(model, optimizer, n=3, seed=3):
    rng = np.random.default_rng(seed)
    losses = []
    for _ in range(n):
        x = paddle.to_tensor(rng.standard_normal((8, 16)).astype("float32"))
        y = paddle.to_tensor(rng.standard_normal((8, 8)).astype("float32"))
        loss = ((model(x) - y) ** 2).mean()
        loss.backward()
        optimizer.step()
        optimizer.clear_grad()
        losses.append(float(loss))
    return losses


def _shard_axis_sizes(arr):
    """Number of distinct devices the array's dim-0 is split across."""
    sharding = arr.sharding
    spec = getattr(sharding, "spec", None)
    return spec


class TestGroupShardedParallel:
    @pytest.mark.parametrize("level", ["os", "os_g", "p_g_os"])
    def test_matches_unsharded_training(self, level):
        base_model = _make_model()
        base_opt = opt.AdamW(learning_rate=0.01,
                             parameters=base_model.parameters())
        base_losses = _train_steps(base_model, base_opt)

        model = _make_model()
        optimizer = opt.AdamW(learning_rate=0.01,
                              parameters=model.parameters())
        model, optimizer, _ = dist.group_sharded_parallel(
            model, optimizer, level
        )
        losses = _train_steps(model, optimizer)
        np.testing.assert_allclose(losses, base_losses, rtol=2e-5, atol=1e-6)

    def test_stage1_states_sharded(self):
        model = _make_model()
        optimizer = opt.AdamW(learning_rate=0.01,
                              parameters=model.parameters())
        model, optimizer, _ = dist.group_sharded_parallel(
            model, optimizer, "os"
        )
        _train_steps(model, optimizer, n=1)
        # dim0=16 and 32 divide 8 → moments must be sharded on dim 0
        sharded = 0
        for store in optimizer._accumulators.values():
            for arr in store.values():
                spec = arr.sharding.spec if hasattr(arr.sharding, "spec") \
                    else None
                if spec and len(spec) > 0 and spec[0] == "sharding":
                    sharded += 1
        assert sharded > 0, "no optimizer state ended up sharded"
        # params stay replicated at stage 1
        for p in model.parameters():
            spec = getattr(p._value.sharding, "spec", None)
            if spec:
                assert all(s is None for s in spec), \
                    f"stage-1 param unexpectedly sharded: {spec}"

    def test_stage3_params_sharded(self):
        model = _make_model()
        optimizer = opt.AdamW(learning_rate=0.01,
                              parameters=model.parameters())
        model, optimizer, _ = dist.group_sharded_parallel(
            model, optimizer, "p_g_os"
        )
        sharded_params = 0
        for p in model.parameters():
            spec = getattr(p._value.sharding, "spec", None)
            if spec and len(spec) > 0 and spec[0] == "sharding":
                sharded_params += 1
        assert sharded_params > 0, "no parameter ended up sharded at stage 3"
        # training still works on sharded params
        losses = _train_steps(model, optimizer, n=2)
        assert all(np.isfinite(losses))

    def test_bad_level_rejected(self):
        model = _make_model()
        optimizer = opt.AdamW(learning_rate=0.01,
                              parameters=model.parameters())
        with pytest.raises(ValueError):
            dist.group_sharded_parallel(model, optimizer, "zeRO-9")

    def test_save_group_sharded_model(self, tmp_path):
        model = _make_model()
        optimizer = opt.AdamW(learning_rate=0.01,
                              parameters=model.parameters())
        model, optimizer, _ = dist.group_sharded_parallel(
            model, optimizer, "p_g_os"
        )
        _train_steps(model, optimizer, n=1)
        out = tmp_path / "ckpt"
        dist.save_group_sharded_model(model, str(out), optimizer)
        state = paddle.load(str(out / "model.pdparams"))
        fresh = _make_model(seed=99)
        fresh.set_state_dict(state)
        for (n1, p), (n2, q) in zip(
            model.named_parameters(), fresh.named_parameters()
        ):
            np.testing.assert_allclose(
                np.asarray(p._value), np.asarray(q._value), rtol=1e-6
            )


class TestFleetShardingIntegration:
    @pytest.fixture(autouse=True)
    def _reset_fleet(self):
        # `fleet.init` leaves its topology behind as process state: a MoE
        # layer built later in the same worker would take its "mp" axis
        yield
        dist.fleet.set_hybrid_communicate_group(None)

    def test_hybrid_topology_sharding_axis(self):
        import paddle_tpu.distributed.fleet as fleet

        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {
            "dp_degree": 1, "mp_degree": 2, "pp_degree": 1,
            "sharding_degree": 4, "sep_degree": 1,
        }
        fleet.init(is_collective=True, strategy=strategy)
        model = _make_model()
        model = fleet.distributed_model(model)
        optimizer = opt.AdamW(learning_rate=0.01,
                              parameters=model.parameters())
        optimizer = fleet.distributed_optimizer(optimizer)
        losses = _train_steps(model, optimizer, n=2)
        assert all(np.isfinite(losses))
        # moments sharded over the 4-way sharding axis
        inner = optimizer._inner_opt
        sharded = 0
        for store in inner._accumulators.values():
            for arr in store.values():
                spec = getattr(arr.sharding, "spec", None)
                if spec and len(spec) > 0 and spec[0] == "sharding":
                    sharded += 1
        assert sharded > 0

    def test_group_sharded_stage2_classes(self):
        from paddle_tpu.distributed.fleet.meta_parallel import (
            GroupShardedOptimizerStage2, GroupShardedStage2,
        )

        model = _make_model()
        inner = opt.AdamW(learning_rate=0.01,
                          parameters=model.parameters())
        sh_opt = GroupShardedOptimizerStage2(
            list(model.parameters()), inner
        )
        wrapped = GroupShardedStage2(model, sh_opt)
        losses = _train_steps(wrapped, sh_opt, n=2)
        assert all(np.isfinite(losses))

    def test_jitted_sharded_step(self):
        """The whole ZeRO-2 step under jit — grads constrained in-trace."""
        model = _make_model()
        optimizer = opt.AdamW(learning_rate=0.01,
                              parameters=model.parameters())
        model, optimizer, _ = dist.group_sharded_parallel(
            model, optimizer, "os_g"
        )

        @paddle.jit.to_static
        def step(x, y):
            loss = ((model(x) - y) ** 2).mean()
            loss.backward()
            optimizer.step()
            optimizer.clear_grad()
            return loss

        rng = np.random.default_rng(0)
        x = paddle.to_tensor(rng.standard_normal((8, 16)).astype("float32"))
        y = paddle.to_tensor(rng.standard_normal((8, 8)).astype("float32"))
        l1 = float(step(x, y))
        l2 = float(step(x, y))
        assert np.isfinite(l1) and l2 < l1
