"""Op unit tests: math/reduction/linalg/manipulation vs numpy, with grad
checks (modelled on the reference OpTest suite, SURVEY.md §4.1)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from op_test import check_output, check_grad

rng = np.random.default_rng(0)


def r(*shape):
    return rng.standard_normal(shape).astype(np.float32)


def rp(*shape):
    return (rng.random(shape).astype(np.float32) + 0.5)


class TestUnaryOps:
    @pytest.mark.parametrize("name", [
        "abs", "exp", "log1p", "sqrt", "square", "sin", "cos", "tanh",
        "floor", "ceil", "sign", "reciprocal", "erf", "sigmoid", "rsqrt",
    ])
    def test_forward(self, name):
        x = rp(3, 4)
        np_map = {
            "sigmoid": lambda v: 1 / (1 + np.exp(-v)),
            "erf": lambda v: np.vectorize(__import__("math").erf)(v).astype(np.float32),
            "rsqrt": lambda v: 1 / np.sqrt(v),
            "square": np.square, "reciprocal": np.reciprocal,
        }
        np_fn = np_map.get(name, getattr(np, name, None))
        check_output(getattr(paddle, name), lambda v: np_fn(v), [x],
                     rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("name", ["exp", "tanh", "sqrt", "sigmoid", "log"])
    def test_grad(self, name):
        x = rp(3, 4)
        check_grad(getattr(paddle, name), [x])


class TestBinaryOps:
    @pytest.mark.parametrize("name,np_fn", [
        ("add", np.add), ("subtract", np.subtract),
        ("multiply", np.multiply), ("divide", np.divide),
        ("maximum", np.maximum), ("minimum", np.minimum),
        ("pow", np.power),
    ])
    def test_forward(self, name, np_fn):
        x, y = rp(3, 4), rp(3, 4)
        check_output(getattr(paddle, name), np_fn, [x, y])

    def test_broadcast(self):
        x, y = r(3, 1, 4), r(5, 1)
        check_output(paddle.add, np.add, [x, y])

    @pytest.mark.parametrize("name", ["add", "multiply", "divide", "subtract"])
    def test_grad(self, name):
        check_grad(getattr(paddle, name), [rp(3, 4), rp(3, 4)])

    def test_grad_broadcast(self):
        check_grad(paddle.multiply, [rp(3, 4), rp(4)])

    def test_scalar_dtype_rule(self):
        x = paddle.ones([2], dtype="float32")
        assert (x + 1).dtype == paddle.float32
        assert (x * 2.5).dtype == paddle.float32
        xi = paddle.ones([2], dtype="int64")
        assert (xi + 1).dtype == paddle.int64


class TestReductions:
    @pytest.mark.parametrize("name,np_fn", [
        ("sum", np.sum), ("mean", np.mean), ("max", np.max), ("min", np.min),
        ("prod", np.prod),
    ])
    @pytest.mark.parametrize("axis,keepdim", [(None, False), (0, False),
                                              (1, True), ([0, 1], False)])
    def test_forward(self, name, np_fn, axis, keepdim):
        x = r(3, 4, 5)
        want = np_fn(x, axis=tuple(axis) if isinstance(axis, list) else axis,
                     keepdims=keepdim)
        got = getattr(paddle, name)(paddle.to_tensor(x), axis=axis,
                                    keepdim=keepdim)
        np.testing.assert_allclose(got.numpy(), want, rtol=1e-5, atol=1e-5)

    def test_grad(self):
        check_grad(lambda x: paddle.sum(x, axis=1), [r(3, 4)])
        check_grad(lambda x: paddle.mean(x, axis=0, keepdim=True), [r(3, 4)])
        check_grad(lambda x: paddle.max(x, axis=1), [rp(3, 4)], rtol=2e-2)

    def test_argmax(self):
        x = r(3, 4)
        assert paddle.argmax(paddle.to_tensor(x), axis=1).numpy().tolist() == \
            np.argmax(x, axis=1).tolist()

    def test_cumsum(self):
        x = r(3, 4)
        check_output(paddle.cumsum, lambda v, axis=1: np.cumsum(v, axis=1),
                     [x], axis=1)
        check_grad(lambda t: paddle.cumsum(t, axis=0), [x])

    def test_std_var(self):
        x = r(5, 6)
        np.testing.assert_allclose(paddle.std(paddle.to_tensor(x)).item(),
                                   np.std(x, ddof=1), rtol=1e-4)
        np.testing.assert_allclose(
            paddle.var(paddle.to_tensor(x), axis=1).numpy(),
            np.var(x, axis=1, ddof=1), rtol=1e-4, atol=1e-5)

    def test_logsumexp(self):
        from scipy.special import logsumexp as np_lse
        x = r(3, 4)
        np.testing.assert_allclose(
            paddle.logsumexp(paddle.to_tensor(x), axis=1).numpy(),
            np_lse(x, axis=1), rtol=1e-5)


class TestMatmul:
    @pytest.mark.parametrize("sx,sy,tx,ty", [
        ((3, 4), (4, 5), False, False),
        ((4, 3), (4, 5), True, False),
        ((3, 4), (5, 4), False, True),
        ((2, 3, 4), (2, 4, 5), False, False),
        ((4,), (4,), False, False),
        ((2, 3, 4), (4,), False, False),
    ])
    def test_forward(self, sx, sy, tx, ty):
        x, y = r(*sx), r(*sy)
        xx = np.swapaxes(x, -1, -2) if tx else x
        yy = np.swapaxes(y, -1, -2) if ty else y
        check_output(paddle.matmul, lambda a, b, transpose_x=0,
                     transpose_y=0: np.matmul(xx, yy), [x, y],
                     transpose_x=tx, transpose_y=ty)

    def test_grad(self):
        check_grad(paddle.matmul, [r(3, 4), r(4, 5)])
        check_grad(lambda x, y: paddle.matmul(x, y, transpose_y=True),
                   [r(3, 4), r(5, 4)])


class TestManipulation:
    def test_reshape_transpose(self):
        x = r(2, 3, 4)
        assert paddle.reshape(paddle.to_tensor(x), [4, 6]).shape == [4, 6]
        assert paddle.transpose(paddle.to_tensor(x), [2, 0, 1]).shape == [4, 2, 3]
        check_grad(lambda t: paddle.reshape(t, [-1]), [x])
        check_grad(lambda t: paddle.transpose(t, [1, 0, 2]), [x])

    def test_concat_split_stack(self):
        xs = [r(2, 3), r(2, 3)]
        got = paddle.concat([paddle.to_tensor(v) for v in xs], axis=1)
        np.testing.assert_allclose(got.numpy(), np.concatenate(xs, 1))
        got = paddle.stack([paddle.to_tensor(v) for v in xs], axis=0)
        np.testing.assert_allclose(got.numpy(), np.stack(xs, 0))
        parts = paddle.split(paddle.to_tensor(r(6, 3)), 3, axis=0)
        assert len(parts) == 3 and parts[0].shape == [2, 3]
        parts = paddle.split(paddle.to_tensor(r(7, 3)), [2, -1], axis=0)
        assert parts[1].shape == [5, 3]
        check_grad(lambda a, b: paddle.concat([a, b], axis=0), [r(2, 3), r(4, 3)])

    def test_gather_scatter(self):
        x = r(5, 3)
        idx = np.array([0, 2, 4])
        np.testing.assert_allclose(
            paddle.gather(paddle.to_tensor(x), paddle.to_tensor(idx)).numpy(),
            x[idx])
        upd = r(3, 3)
        got = paddle.scatter(paddle.to_tensor(x), paddle.to_tensor(idx),
                             paddle.to_tensor(upd))
        want = x.copy()
        want[idx] = upd
        np.testing.assert_allclose(got.numpy(), want)
        check_grad(lambda t: paddle.gather(t, paddle.to_tensor(idx)), [x])

    def test_where_masked(self):
        x, y = r(3, 4), r(3, 4)
        c = x > 0
        np.testing.assert_allclose(
            paddle.where(paddle.to_tensor(c), paddle.to_tensor(x),
                         paddle.to_tensor(y)).numpy(),
            np.where(c, x, y))
        np.testing.assert_allclose(
            paddle.masked_select(paddle.to_tensor(x),
                                 paddle.to_tensor(c)).numpy(),
            x[c])

    def test_tile_expand(self):
        x = r(1, 3)
        np.testing.assert_allclose(
            paddle.tile(paddle.to_tensor(x), [2, 2]).numpy(), np.tile(x, (2, 2)))
        assert paddle.expand(paddle.to_tensor(x), [4, 3]).shape == [4, 3]

    def test_pad(self):
        x = r(2, 3, 4, 5)
        got = paddle.ops.manipulation.pad(paddle.to_tensor(x), [1, 2, 3, 4])
        want = np.pad(x, [(0, 0), (0, 0), (3, 4), (1, 2)])
        np.testing.assert_allclose(got.numpy(), want)

    def test_getitem_grad(self):
        x = r(4, 5)
        t = paddle.to_tensor(x, stop_gradient=False)
        y = t[1:3, ::2]
        y.sum().backward()
        want = np.zeros_like(x)
        want[1:3, ::2] = 1
        np.testing.assert_allclose(t.grad.numpy(), want)

    def test_topk_sort(self):
        x = r(3, 8)
        v, i = paddle.topk(paddle.to_tensor(x), 3, axis=1)
        np.testing.assert_allclose(v.numpy(), -np.sort(-x, axis=1)[:, :3],
                                   rtol=1e-6)
        s = paddle.sort(paddle.to_tensor(x), axis=1, descending=True)
        np.testing.assert_allclose(s.numpy(), -np.sort(-x, axis=1), rtol=1e-6)


class TestComparison:
    def test_ops(self):
        x, y = r(3, 4), r(3, 4)
        tx, ty = paddle.to_tensor(x), paddle.to_tensor(y)
        assert ((tx > ty).numpy() == (x > y)).all()
        assert ((tx == tx).numpy()).all()
        assert bool(paddle.allclose(tx, tx))
        assert not bool(paddle.equal_all(tx, ty))


class TestAutogradEngine:
    def test_diamond(self):
        x = paddle.to_tensor(r(3, 3), stop_gradient=False)
        a = x * 2
        b = x + 1
        (a * b).sum().backward()
        want = 4 * x.numpy() + 2
        np.testing.assert_allclose(x.grad.numpy(), want, rtol=1e-5)

    def test_accumulation(self):
        x = paddle.to_tensor(r(2, 2), stop_gradient=False)
        (x * 1.0).sum().backward()
        (x * 2.0).sum().backward()
        np.testing.assert_allclose(x.grad.numpy(), np.full((2, 2), 3.0))
        x.clear_grad()
        assert x.grad is None

    def test_no_grad(self):
        x = paddle.to_tensor(r(2, 2), stop_gradient=False)
        with paddle.no_grad():
            y = x * 2
        assert y.stop_gradient

    def test_detach(self):
        x = paddle.to_tensor(r(2, 2), stop_gradient=False)
        y = (x * 2).detach()
        assert y.stop_gradient and y.is_leaf

    def test_retain_grads(self):
        x = paddle.to_tensor(r(2, 2), stop_gradient=False)
        y = x * 3
        y.retain_grads()
        y.sum().backward()
        np.testing.assert_allclose(y.grad.numpy(), np.ones((2, 2)))

    def test_grad_api(self):
        x = paddle.to_tensor(r(2, 2), stop_gradient=False)
        y = paddle.to_tensor(r(2, 2), stop_gradient=False)
        out = (x * y).sum()
        gx, = paddle.grad(out, [x])
        np.testing.assert_allclose(gx.numpy(), y.numpy())
        assert x.grad is None  # paddle.grad must not touch .grad

    def test_hook(self):
        x = paddle.to_tensor(r(2, 2), stop_gradient=False)
        seen = []
        x.register_hook(lambda g: seen.append(g.shape))
        (x * 2).sum().backward()
        assert seen == [[2, 2]]

    def test_second_use_after_inplace(self):
        # in-place rebind must not corrupt saved tensors
        x = paddle.to_tensor(np.full((2, 2), 2.0, np.float32),
                             stop_gradient=False)
        y = x * x          # saves x=2
        x.add_(paddle.to_tensor(np.ones((2, 2), np.float32)))  # x now 3
        y.sum().backward()
        # dy/dx at the saved value 2: grad = 2*2 = 4
        np.testing.assert_allclose(x.grad.numpy(), np.full((2, 2), 4.0))


class TestCreation:
    def test_basics(self):
        assert paddle.zeros([2, 3]).numpy().sum() == 0
        assert paddle.ones([2], dtype="int32").dtype == paddle.int32
        assert paddle.full([2], 7).numpy().tolist() == [7, 7]
        assert paddle.arange(5).numpy().tolist() == [0, 1, 2, 3, 4]
        assert paddle.linspace(0, 1, 5).shape == [5]
        e = paddle.eye(3).numpy()
        np.testing.assert_allclose(e, np.eye(3, dtype=np.float32))

    def test_like(self):
        x = paddle.ones([2, 3], dtype="float32")
        assert paddle.zeros_like(x).shape == [2, 3]
        assert paddle.full_like(x, 2.0).numpy()[0, 0] == 2.0

    def test_random_determinism(self):
        paddle.seed(42)
        a = paddle.rand([3, 3]).numpy()
        paddle.seed(42)
        b = paddle.rand([3, 3]).numpy()
        np.testing.assert_allclose(a, b)
        assert paddle.randn([100]).numpy().std() > 0.5
        ri = paddle.randint(0, 10, [100]).numpy()
        assert ri.min() >= 0 and ri.max() < 10
        p = paddle.randperm(10).numpy()
        assert sorted(p.tolist()) == list(range(10))

    def test_tril_triu(self):
        x = r(4, 4)
        np.testing.assert_allclose(paddle.tril(paddle.to_tensor(x)).numpy(),
                                   np.tril(x))
        np.testing.assert_allclose(
            paddle.triu(paddle.to_tensor(x), 1).numpy(), np.triu(x, 1))


class TestLinalg:
    def test_solve_inv_det(self):
        a = r(4, 4) + 4 * np.eye(4, dtype=np.float32)
        b = r(4, 2)
        np.testing.assert_allclose(
            paddle.linalg.solve(paddle.to_tensor(a), paddle.to_tensor(b)).numpy(),
            np.linalg.solve(a, b), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(
            paddle.linalg.inv(paddle.to_tensor(a)).numpy(),
            np.linalg.inv(a), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(
            paddle.linalg.det(paddle.to_tensor(a)).item(),
            np.linalg.det(a), rtol=1e-3)

    def test_svd_qr_eigh_cholesky(self):
        a = r(5, 3)
        u, s, v = paddle.linalg.svd(paddle.to_tensor(a))
        rec = u.numpy() @ np.diag(s.numpy()) @ v.numpy().T
        np.testing.assert_allclose(rec, a, rtol=1e-4, atol=1e-4)
        q, rr = paddle.linalg.qr(paddle.to_tensor(a))
        np.testing.assert_allclose(q.numpy() @ rr.numpy(), a, rtol=1e-4,
                                   atol=1e-4)
        sym = a.T @ a + np.eye(3, dtype=np.float32)
        w, vec = paddle.linalg.eigh(paddle.to_tensor(sym))
        np.testing.assert_allclose(
            vec.numpy() @ np.diag(w.numpy()) @ vec.numpy().T, sym,
            rtol=1e-4, atol=1e-4)
        c = paddle.linalg.cholesky(paddle.to_tensor(sym))
        np.testing.assert_allclose(c.numpy() @ c.numpy().T, sym, rtol=1e-4,
                                   atol=1e-4)

    def test_norm_einsum(self):
        x = r(3, 4)
        np.testing.assert_allclose(paddle.linalg.norm(paddle.to_tensor(x)).item(),
                                   np.linalg.norm(x), rtol=1e-5)
        y = r(4, 5)
        np.testing.assert_allclose(
            paddle.ops.linalg.einsum("ij,jk->ik", paddle.to_tensor(x),
                                     paddle.to_tensor(y)).numpy(),
            x @ y, rtol=1e-5, atol=1e-5)


class TestDtypeSweep:
    """bf16/fp16 coverage through the math zoo, against an f64 numpy
    reference (review r3 weak #5: nothing previously swept bf16
    through ops/math.py; f64 tensors are f32 by to_tensor policy)."""

    CASES = [
        ("add", lambda a, b: paddle.add(a, b), lambda a, b: a + b, 2),
        ("subtract", lambda a, b: paddle.subtract(a, b),
         lambda a, b: a - b, 2),
        ("multiply", lambda a, b: paddle.multiply(a, b),
         lambda a, b: a * b, 2),
        ("divide", lambda a, b: paddle.divide(a, b + 2.0),
         lambda a, b: a / (b + 2.0), 2),
        ("maximum", lambda a, b: paddle.maximum(a, b), np.maximum, 2),
        ("minimum", lambda a, b: paddle.minimum(a, b), np.minimum, 2),
        ("exp", lambda a: paddle.exp(a), np.exp, 1),
        ("log", lambda a: paddle.log(a + 2.0),
         lambda a: np.log(a + 2.0), 1),
        ("sqrt", lambda a: paddle.sqrt(a + 2.0),
         lambda a: np.sqrt(a + 2.0), 1),
        ("tanh", lambda a: paddle.tanh(a), np.tanh, 1),
        ("sigmoid", lambda a: paddle.nn.functional.sigmoid(a),
         lambda a: 1 / (1 + np.exp(-a)), 1),
        ("abs", lambda a: paddle.abs(a), np.abs, 1),
        ("floor", lambda a: paddle.floor(a), np.floor, 1),
        ("square", lambda a: paddle.square(a), np.square, 1),
        ("reciprocal", lambda a: paddle.reciprocal(a + 2.0),
         lambda a: 1.0 / (a + 2.0), 1),
        ("pow", lambda a: paddle.pow(a + 2.0, 2.0),
         lambda a: (a + 2.0) ** 2.0, 1),
        ("mean", lambda a: paddle.mean(a), np.mean, 1),
        ("sum", lambda a: paddle.sum(a), np.sum, 1),
        ("matmul", lambda a, b: paddle.matmul(a, b.T + 0.0),
         lambda a, b: a @ b.T, 2),
    ]

    @pytest.mark.parametrize("name,api,ref,nin",
                             CASES, ids=[c[0] for c in CASES])
    def test_dtype_sweep(self, name, api, ref, nin):
        from op_test import check_dtypes
        rng = np.random.RandomState(0)
        ins = [rng.randn(4, 6).astype("float64") * 0.5
               for _ in range(nin)]
        check_dtypes(api, ref, ins, grad=name not in ("floor",))


class TestEagerStaticParity:
    """Every op produces identical results recorded into a Program and
    replayed by the Executor (reference op_test's dual-executor run)."""

    CASES = [
        ("add", lambda a, b: paddle.add(a, b), 2),
        ("multiply", lambda a, b: paddle.multiply(a, b), 2),
        ("matmul", lambda a, b: paddle.matmul(a, b), 2),
        ("exp", lambda a: paddle.exp(a), 1),
        ("tanh", lambda a: paddle.tanh(a), 1),
        ("softmax", lambda a: paddle.nn.functional.softmax(a), 1),
        ("relu", lambda a: paddle.nn.functional.relu(a), 1),
        ("mean_axis", lambda a: paddle.mean(a, axis=1), 1),
        ("cumsum", lambda a: paddle.cumsum(a, axis=-1), 1),
        ("topk_values", lambda a: paddle.topk(a, 3)[0], 1),
        ("concat_self", lambda a: paddle.concat([a, a], axis=0), 1),
        ("transpose", lambda a: paddle.transpose(a, [1, 0]), 1),
        ("layer_norm", lambda a: paddle.nn.functional.layer_norm(
            a, a.shape[-1]), 1),
        ("clip", lambda a: paddle.clip(a, -0.5, 0.5), 1),
        ("log_softmax", lambda a: paddle.nn.functional.log_softmax(a),
         1),
    ]

    @pytest.mark.parametrize("name,api,nin", CASES,
                             ids=[c[0] for c in CASES])
    def test_eager_static_parity(self, name, api, nin):
        from op_test import check_static
        rng = np.random.RandomState(1)
        ins = [rng.randn(6, 6).astype("float32") for _ in range(nin)]
        check_static(api, ins)


class TestTakeAndMethodParity:
    def test_take_modes(self):
        x = paddle.to_tensor(np.arange(12, dtype="float32").reshape(3, 4))
        idx = paddle.to_tensor(np.array([[0, 5], [11, -1]], "int64"))
        out = paddle.take(x, idx)
        np.testing.assert_array_equal(out.numpy(),
                                      [[0.0, 5.0], [11.0, 11.0]])
        wrap = paddle.take(x, paddle.to_tensor(
            np.array([12, -13], "int64")), mode="wrap")
        np.testing.assert_array_equal(wrap.numpy(), [0.0, 11.0])
        clip = paddle.take(x, paddle.to_tensor(
            np.array([25, -40, -1], "int64")), mode="clip")
        # reference clip semantics: raw index clipped to [0, n-1]
        np.testing.assert_array_equal(clip.numpy(), [11.0, 0.0, 0.0])
        with pytest.raises(IndexError):
            paddle.take(x, paddle.to_tensor(np.array([12], "int64")))
        with pytest.raises(TypeError):
            paddle.take(x, paddle.to_tensor(
                np.array([1.5], "float32")))
        empty = paddle.take(x, paddle.to_tensor(
            np.array([], "int64")))
        assert empty.shape == [0]

    def test_trivial_method_parity(self):
        t = paddle.to_tensor(np.ones((2, 3), "float32"))
        assert t.ndimension() == 2
        assert t.is_floating_point()
        assert not paddle.to_tensor(np.ones(2, "int64")).is_floating_point()
        assert t.cpu() is t and t.cuda() is t and t.pin_memory() is t
        assert t.is_contiguous() and t.contiguous() is t
