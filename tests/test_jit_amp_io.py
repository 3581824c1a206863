"""jit.to_static, amp, DataLoader, save/load tests (reference:
test/dygraph_to_static/, test/amp/, test/legacy_test/test_dataloader_*)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as opt


def _r(*shape):
    return np.random.randn(*shape).astype("float32")


class TestToStatic:
    def test_forward_capture_matches_eager(self):
        model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
        model.eval()
        x = paddle.to_tensor(_r(3, 8))
        eager = model(x).numpy()

        fwd = paddle.jit.to_static(lambda t: model(t))
        static = fwd(x).numpy()
        np.testing.assert_allclose(eager, static, atol=1e-5)

    def test_recompile_on_new_shape(self):
        model = nn.Linear(4, 2)
        fwd = paddle.jit.to_static(lambda t: model(t))
        assert fwd(paddle.to_tensor(_r(2, 4))).shape == [2, 2]
        assert fwd(paddle.to_tensor(_r(7, 4))).shape == [7, 2]
        assert len(fwd._cache) == 2

    def test_param_update_visible_to_compiled_fn(self):
        model = nn.Linear(4, 1, bias_attr=False)
        fwd = paddle.jit.to_static(lambda t: model(t))
        x = paddle.to_tensor(np.ones((1, 4), np.float32))
        y1 = float(fwd(x))
        model.weight.set_value(model.weight.numpy() * 2)
        y2 = float(fwd(x))
        np.testing.assert_allclose(y2, 2 * y1, rtol=1e-5)

    def test_full_train_step_matches_eager(self):
        paddle.seed(3)
        m1 = nn.Sequential(nn.Linear(8, 8), nn.Tanh(), nn.Linear(8, 1))
        paddle.seed(3)
        m2 = nn.Sequential(nn.Linear(8, 8), nn.Tanh(), nn.Linear(8, 1))
        np.testing.assert_allclose(m1[0].weight.numpy(), m2[0].weight.numpy())
        o1 = opt.AdamW(0.01, parameters=m1.parameters())
        o2 = opt.AdamW(0.01, parameters=m2.parameters())
        loss_fn = nn.MSELoss()
        X, Y = _r(16, 8), _r(16, 1)

        @paddle.jit.to_static
        def step2(x, y):
            loss = loss_fn(m2(x), y)
            loss.backward()
            o2.step()
            o2.clear_grad()
            return loss

        for i in range(5):
            xb, yb = paddle.to_tensor(X), paddle.to_tensor(Y)
            l1 = loss_fn(m1(xb), yb)
            l1.backward()
            o1.step()
            o1.clear_grad()
            l2 = step2(xb, yb)
            np.testing.assert_allclose(float(l1), float(l2), rtol=2e-4)
        np.testing.assert_allclose(
            m1[0].weight.numpy(), m2[0].weight.numpy(), atol=2e-5
        )

    def test_decorated_layer(self):
        class M(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc = nn.Linear(4, 2)

            def forward(self, x):
                return self.fc(x)

        m = paddle.jit.to_static(M())
        assert m(paddle.to_tensor(_r(3, 4))).shape == [3, 2]

    def test_dropout_rng_varies_under_jit(self):
        drop = nn.Dropout(0.5)
        f = paddle.jit.to_static(lambda t: drop(t))
        x = paddle.to_tensor(np.ones((100,), np.float32))
        a = f(x).numpy()
        b = f(x).numpy()
        assert not np.array_equal(a, b)  # fresh key each call


class TestJitSaveLoad:
    def test_save_load_inference(self, tmp_path):
        model = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
        model.eval()
        path = str(tmp_path / "infer")
        paddle.jit.save(model, path, input_spec=[paddle.static.InputSpec([3, 4])])
        loaded = paddle.jit.load(path)
        x = _r(3, 4)
        want = model(paddle.to_tensor(x)).numpy()
        got = loaded(paddle.to_tensor(x)).numpy()
        np.testing.assert_allclose(got, want, atol=1e-5)


class TestAmp:
    def test_autocast_casts_matmul(self):
        x = paddle.to_tensor(_r(4, 4))
        with paddle.amp.auto_cast(level="O1"):
            y = paddle.matmul(x, x)
        assert str(y.dtype) == "bfloat16"
        z = paddle.matmul(x, x)
        assert str(z.dtype) == "float32"

    def test_blacklist_stays_fp32(self):
        x = paddle.to_tensor(_r(4, 4))
        with paddle.amp.auto_cast(level="O1"):
            s = paddle.nn.functional.softmax(x)
        assert str(s.dtype) == "float32"

    def test_grad_scaler_fp16_flow(self):
        model = nn.Linear(4, 1)
        o = opt.SGD(0.01, parameters=model.parameters())
        scaler = paddle.amp.GradScaler(init_loss_scaling=128.0)
        loss = model(paddle.to_tensor(_r(8, 4))).mean()
        scaled = scaler.scale(loss)
        scaled.backward()
        scaler.step(o)
        scaler.update()
        assert scaler.get_loss_scaling().numpy() > 0

    def test_scaler_skips_on_inf(self):
        model = nn.Linear(2, 1)
        o = opt.SGD(0.01, parameters=model.parameters())
        w_before = model.weight.numpy().copy()
        scaler = paddle.amp.GradScaler(init_loss_scaling=4.0)
        model.weight._grad_value = paddle.to_tensor(
            np.array([[np.inf], [1.0]], np.float32)
        )._value
        model.bias._grad_value = paddle.to_tensor(np.zeros(1, np.float32))._value
        scaler.step(o)
        scaler.update()
        np.testing.assert_allclose(model.weight.numpy(), w_before)
        assert scaler._scale < 4.0


class TestDataLoader:
    def test_basic_batching(self):
        from paddle_tpu.io import DataLoader, Dataset

        class DS(Dataset):
            def __getitem__(self, i):
                return np.full((3,), i, np.float32), np.int64(i % 2)

            def __len__(self):
                return 10

        dl = DataLoader(DS(), batch_size=4, drop_last=False)
        batches = list(dl)
        assert len(batches) == 3
        x, y = batches[0]
        assert x.shape == [4, 3] and y.shape == [4]

    def test_shuffle_and_workers(self):
        from paddle_tpu.io import DataLoader, TensorDataset

        data = paddle.to_tensor(np.arange(32, dtype=np.float32).reshape(32, 1))
        ds = TensorDataset([data])
        dl = DataLoader(ds, batch_size=8, shuffle=True, num_workers=2)
        seen = np.sort(np.concatenate([b[0].numpy().ravel() for b in dl]))
        np.testing.assert_array_equal(seen, np.arange(32))

    def test_distributed_batch_sampler(self):
        from paddle_tpu.io import DataLoader, Dataset, DistributedBatchSampler

        class DS(Dataset):
            def __getitem__(self, i):
                return np.float32(i)

            def __len__(self):
                return 16

        parts = []
        for rank in range(2):
            bs = DistributedBatchSampler(DS(), 4, num_replicas=2, rank=rank)
            dl = DataLoader(DS(), batch_sampler=bs)
            parts.append(np.concatenate([b.numpy() for b in dl]))
        all_seen = np.sort(np.concatenate(parts))
        np.testing.assert_array_equal(all_seen, np.arange(16, dtype=np.float32))


class TestSaveLoad:
    def test_nested_objects(self, tmp_path):
        obj = {
            "w": paddle.to_tensor(_r(3, 3)),
            "list": [paddle.to_tensor(_r(2)), 5, "s"],
            "scalar": 1.5,
        }
        p = str(tmp_path / "obj.pd")
        paddle.save(obj, p)
        loaded = paddle.load(p)
        np.testing.assert_allclose(loaded["w"].numpy(), obj["w"].numpy())
        assert loaded["list"][1] == 5 and loaded["scalar"] == 1.5

    def test_bf16_roundtrip(self, tmp_path):
        x = paddle.to_tensor(_r(4)).astype("bfloat16")
        p = str(tmp_path / "bf16.pd")
        paddle.save({"x": x}, p)
        loaded = paddle.load(p)
        assert str(loaded["x"].dtype) == "bfloat16"


class TestHostInit:
    """host_init + to_accelerator: host-side construction with one bulk
    device_put (the LazyGuard/LazyInit analog)."""

    def test_host_init_builds_and_bulk_moves(self):
        import jax

        with paddle.device.host_init():
            m = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
        # on the CPU test backend this is a no-op move; the API contract
        # is: parameters remain usable and numerically identical
        before = [p.numpy().copy() for p in m.parameters()]
        out = paddle.device.to_accelerator(m)
        assert out is m
        for p, b in zip(m.parameters(), before):
            np.testing.assert_array_equal(p.numpy(), b)
        y = m(paddle.ones([2, 8]))
        assert list(y.shape) == [2, 4]

    def test_to_accelerator_accepts_tensor_list(self):
        ts = [paddle.ones([3]), paddle.zeros([2, 2])]
        out = paddle.device.to_accelerator(ts)
        np.testing.assert_array_equal(out[0].numpy(), np.ones(3, "float32"))


class TestCompileCachePlacement:
    """device/chip.py:setup_compile_cache — the cache directory comes
    from outside when JAX_COMPILATION_CACHE_DIR is set."""

    @pytest.fixture
    def jax_cache_config(self):
        import jax

        names = ("jax_compilation_cache_dir",
                 "jax_persistent_cache_min_compile_time_secs")
        prev = {n: getattr(jax.config, n) for n in names}
        yield jax.config
        for n, v in prev.items():
            jax.config.update(n, v)

    def test_env_dir_is_left_alone(self, monkeypatch, jax_cache_config):
        from paddle_tpu.device.chip import setup_compile_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/from/outside")
        before = jax_cache_config.jax_compilation_cache_dir
        setup_compile_cache()
        # jax read the variable at import (not set then): the helper
        # must not have written any directory of its own over it
        assert jax_cache_config.jax_compilation_cache_dir == before
        assert (jax_cache_config
                .jax_persistent_cache_min_compile_time_secs) <= 1.0

    def test_default_is_checkout_jax_cache(self, monkeypatch,
                                           jax_cache_config):
        import os

        from paddle_tpu.device.chip import setup_compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert setup_compile_cache() == os.path.join(repo, ".jax_cache")
