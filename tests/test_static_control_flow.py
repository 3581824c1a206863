"""static.nn control flow: cond/while_loop/case/switch_case + the
to_static eager-fallback contract.

Reference test models: test/legacy_test/test_cond.py, test_while_loop_op.py,
test_case.py, test_switch_case.py, and the SOT fallback behavior of
dygraph_to_static (program_translator.py:711).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.static.nn as snn


def _t(a, sg=True):
    return paddle.to_tensor(np.asarray(a), stop_gradient=sg)


class TestCondEager:
    def test_scalar_branch(self):
        x = _t(np.array(3.0, dtype="float32"))
        out = snn.cond(x < 5.0, lambda: x + 1.0, lambda: x - 1.0)
        assert float(out) == pytest.approx(4.0)
        out = snn.cond(x > 5.0, lambda: x + 1.0, lambda: x - 1.0)
        assert float(out) == pytest.approx(2.0)

    def test_nested_structure(self):
        x = _t(np.ones((2, 2), dtype="float32"))
        out = snn.cond(_t(True), lambda: [x * 2, {"a": x + 1}],
                       lambda: [x, {"a": x}])
        assert float(out[0].sum()) == pytest.approx(8.0)
        assert float(out[1]["a"].sum()) == pytest.approx(8.0)

    def test_grad_through_taken_branch(self):
        x = _t(np.array([2.0, -1.0], dtype="float32"), sg=False)
        out = snn.cond(_t(True), lambda: (x * x).sum(), lambda: x.sum())
        out.backward()
        np.testing.assert_allclose(x.grad.numpy(), [4.0, -2.0])


class TestCondTraced:
    def test_tensor_dependent_pred_in_jit(self):
        @paddle.jit.to_static
        def f(x):
            return snn.cond(x.sum() > 0, lambda: x * 2.0, lambda: x * -3.0)

        pos = np.ones((3,), dtype="float32")
        neg = -np.ones((3,), dtype="float32")
        np.testing.assert_allclose(f(_t(pos)).numpy(), pos * 2)
        np.testing.assert_allclose(f(_t(neg)).numpy(), neg * -3)

    def test_grads_through_traced_cond(self):
        lin = paddle.nn.Linear(3, 3)
        sgd = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=lin.parameters())

        @paddle.jit.to_static
        def step(x):
            y = lin(x)
            # tensor-dependent branch inside the compiled train step
            loss = snn.cond(y.sum() > 0,
                            lambda: (y * y).mean(),
                            lambda: y.abs().mean())
            loss.backward()
            sgd.step()
            sgd.clear_grad()
            return loss

        w0 = lin.weight.numpy().copy()
        loss = step(_t(np.random.RandomState(0).rand(4, 3).astype("f4")))
        assert np.isfinite(float(loss))
        assert not np.allclose(lin.weight.numpy(), w0), "no update applied"

    def test_branch_structure_mismatch_raises(self):
        @paddle.jit.to_static(full_graph=True)
        def f(x):
            return snn.cond(x.sum() > 0, lambda: [x, x], lambda: x)

        with pytest.raises(Exception):
            f(_t(np.ones((2,), dtype="float32")))


class TestWhileLoop:
    def test_eager_loop(self):
        i = _t(np.array(0, dtype="int64"))
        ten = _t(np.array(10, dtype="int64"))
        out = snn.while_loop(lambda i, t: i < t,
                             lambda i, t: [i + 1, t], [i, ten])
        assert int(out[0]) == 10

    def test_eager_grad_through_loop(self):
        x = _t(np.array(1.5, dtype="float32"), sg=False)
        i = _t(np.array(0, dtype="int64"))

        def body(i, acc):
            return [i + 1, acc * x]

        out = snn.while_loop(lambda i, acc: i < 3, body,
                             [i, _t(np.array(1.0, dtype="float32"))])
        out[1].backward()
        # d(x^3)/dx = 3 x^2
        np.testing.assert_allclose(float(x.grad), 3 * 1.5 ** 2, rtol=1e-6)

    def test_traced_while(self):
        @paddle.jit.to_static
        def f(x):
            # trip count depends on data -> must lower to lax.while_loop
            def cond(v):
                return v.sum() < 100.0

            def body(v):
                return [v * 2.0]

            return snn.while_loop(cond, body, [x])[0]

        out = f(_t(np.ones((4,), dtype="float32")))
        # 4 -> 8 -> 16 -> 32 -> 64 -> 128 (first >= 100)
        np.testing.assert_allclose(out.numpy(), np.full(4, 32.0))

    def test_bad_args(self):
        with pytest.raises(TypeError):
            snn.while_loop(1, lambda: None, [_t(1)])
        with pytest.raises(ValueError):
            snn.while_loop(lambda: True, lambda: None, [])


class TestCaseSwitch:
    def test_case_first_true_wins(self):
        x = _t(np.array(0.3, dtype="float32"))
        out = snn.case([(x < 1.0, lambda: x + 10.0),
                        (x < 2.0, lambda: x + 20.0)],
                       default=lambda: x)
        assert float(out) == pytest.approx(10.3)

    def test_case_default_is_last_fn(self):
        x = _t(np.array(5.0, dtype="float32"))
        out = snn.case([(x < 1.0, lambda: x + 10.0),
                        (x < 2.0, lambda: x + 20.0)])
        # no pred true and default None -> last fn runs
        assert float(out) == pytest.approx(25.0)

    def test_case_traced(self):
        @paddle.jit.to_static
        def f(x):
            return snn.case([(x.sum() < 0, lambda: x - 1.0),
                             (x.sum() < 10, lambda: x + 1.0)],
                            default=lambda: x * 0.0)

        np.testing.assert_allclose(
            f(_t(np.ones(3, dtype="float32"))).numpy(), np.full(3, 2.0))
        np.testing.assert_allclose(
            f(_t(np.full(3, 100.0, dtype="float32"))).numpy(), np.zeros(3))

    def test_switch_case_forms(self):
        idx = _t(np.array(1, dtype="int64"))
        out = snn.switch_case(idx, {1: lambda: _t(10.0), 2: lambda: _t(20.0)},
                              default=lambda: _t(-1.0))
        assert float(out) == pytest.approx(10.0)
        out = snn.switch_case(_t(np.array(7, dtype="int64")),
                              [(1, lambda: _t(10.0)), (2, lambda: _t(20.0))],
                              default=lambda: _t(-1.0))
        assert float(out) == pytest.approx(-1.0)
        # list of plain callables: positional indices; default None -> max key
        out = snn.switch_case(_t(np.array(0, dtype="int64")),
                              [lambda: _t(5.0), lambda: _t(6.0)])
        assert float(out) == pytest.approx(5.0)

    def test_switch_case_traced(self):
        @paddle.jit.to_static
        def f(i, x):
            return snn.switch_case(
                i, {0: lambda: x * 0.0, 1: lambda: x + 1.0},
                default=lambda: x - 1.0)

        x = np.ones(2, dtype="float32")
        np.testing.assert_allclose(
            f(_t(np.array(1, dtype="int64")), _t(x)).numpy(), x + 1)
        np.testing.assert_allclose(
            f(_t(np.array(9, dtype="int64")), _t(x)).numpy(), x - 1)

    def test_switch_duplicate_key_raises(self):
        with pytest.raises(ValueError):
            snn.switch_case(_t(np.array(0, dtype="int64")),
                            [(1, lambda: _t(0.0)), (1, lambda: _t(1.0))])


class TestStaticPylayer:
    def test_custom_backward(self):
        x = _t(np.array([1.0, 2.0], dtype="float32"), sg=False)
        out = snn.static_pylayer(lambda v: v * 2.0, [x],
                                 backward_fn=lambda g: g * 10.0)
        out.sum().backward()
        np.testing.assert_allclose(x.grad.numpy(), [10.0, 10.0])

    def test_no_backward_runs_forward(self):
        x = _t(np.array([3.0], dtype="float32"))
        out = snn.static_pylayer(lambda v: v + 1.0, [x])
        assert float(out) == pytest.approx(4.0)


class TestToStaticFallback:
    def test_python_branch_falls_back(self):
        calls = []

        @paddle.jit.to_static
        def f(x):
            calls.append(1)
            # raw Python branch on a tensor -> untraceable; must fall back
            if float(x.sum()) > 0:
                return x * 2.0
            return x - 1.0

        with pytest.warns(UserWarning, match="falling back to eager"):
            out = f(_t(np.ones(3, dtype="float32")))
        np.testing.assert_allclose(out.numpy(), np.full(3, 2.0))
        # second call with same signature: straight to eager, no retrace
        out = f(_t(np.full(3, 2.0, dtype="float32")))
        np.testing.assert_allclose(out.numpy(), np.full(3, 4.0))

    def test_full_graph_raises(self):
        @paddle.jit.to_static(full_graph=True)
        def f(x):
            if float(x.sum()) > 0:
                return x * 2.0
            return x - 1.0

        with pytest.raises(Exception):
            f(_t(np.ones(3, dtype="float32")))

    def test_grad_through_while_falls_back(self):
        lin = paddle.nn.Linear(2, 2)
        sgd = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=lin.parameters())

        @paddle.jit.to_static
        def step(x):
            y = lin(x)

            def cond(v):
                return v.sum() < 50.0

            def body(v):
                return [v * 2.0]

            out = snn.while_loop(cond, body, [y.abs() + 1.0])[0]
            loss = out.mean()
            loss.backward()
            sgd.step()
            sgd.clear_grad()
            return loss

        # reverse-mode through lax.while_loop is undefined -> eager fallback
        w0 = lin.weight.numpy().copy()
        with pytest.warns(UserWarning, match="falling back to eager"):
            loss = step(_t(np.random.RandomState(1).rand(3, 2).astype("f4")))
        assert np.isfinite(float(loss))
        assert not np.allclose(lin.weight.numpy(), w0)

    def test_device_failure_on_first_call_raises(self, monkeypatch):
        """Only a failure to TRACE may fall back. A JaxRuntimeError —
        what the installed JAX raises for an out-of-memory program, a
        Mosaic refusal or a run-time fault — must come through
        to_static on the first call, even with full_graph=False: an
        eager rerun would hide the device behind a slow step."""
        import jax

        calls = []

        @paddle.jit.to_static
        def f(x):
            calls.append(1)
            return x * 2.0

        class _Refuses:
            def __init__(self, jitted):
                self.trace = jitted.trace

            def __call__(self, *a, **k):
                raise jax.errors.JaxRuntimeError(
                    "RESOURCE_EXHAUSTED: fake: ran out of memory in "
                    "memory space hbm")

        # compile normally, then make the executable refuse to run
        orig_compile = type(f)._compile

        def compile_then_break(self, *a, **k):
            entry = orig_compile(self, *a, **k)
            entry.jitted = _Refuses(entry.jitted)
            return entry

        monkeypatch.setattr(type(f), "_compile", compile_then_break)
        with pytest.raises(jax.errors.JaxRuntimeError,
                           match="RESOURCE_EXHAUSTED"):
            f(_t(np.ones(3, dtype="float32")))
        assert calls == [1]          # traced once, never re-run eagerly

    def test_runtime_error_while_tracing_raises(self):
        import jax

        @paddle.jit.to_static
        def f(x):
            raise jax.errors.JaxRuntimeError("INTERNAL: fake device fault")

        with pytest.raises(jax.errors.JaxRuntimeError):
            f(_t(np.ones(3, dtype="float32")))
