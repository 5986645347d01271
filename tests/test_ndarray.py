"""NDArray basics (reference analog: tests/python/unittest/test_ndarray.py)."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.test_utils import assert_almost_equal, rand_ndarray


def test_creation():
    x = mx.np.array([[1, 2], [3, 4]], dtype="float32")
    assert x.shape == (2, 2)
    assert x.dtype == onp.float32
    assert x.size == 4
    assert x.ndim == 2
    assert_almost_equal(x, onp.array([[1, 2], [3, 4]], dtype="float32"))

    z = mx.np.zeros((3, 4))
    assert z.asnumpy().sum() == 0
    o = mx.np.ones((2, 5), dtype="int32")
    assert o.asnumpy().sum() == 10
    f = mx.np.full((2, 2), 7.0)
    assert f.asnumpy().mean() == 7.0
    a = mx.np.arange(5)
    assert a.shape == (5,)
    e = mx.np.eye(3)
    assert e.asnumpy().trace() == 3.0


def test_elementwise_arith():
    a = mx.np.array([1.0, 2.0, 3.0])
    b = mx.np.array([4.0, 5.0, 6.0])
    assert_almost_equal(a + b, onp.array([5, 7, 9.0]))
    assert_almost_equal(a - b, onp.array([-3, -3, -3.0]))
    assert_almost_equal(a * b, onp.array([4, 10, 18.0]))
    assert_almost_equal(b / a, onp.array([4, 2.5, 2.0]))
    assert_almost_equal(a ** 2, onp.array([1, 4, 9.0]))
    assert_almost_equal(2 + a, onp.array([3, 4, 5.0]))
    assert_almost_equal(2 * a, onp.array([2, 4, 6.0]))
    assert_almost_equal(1 / a, onp.array([1, 0.5, 1 / 3]))
    assert_almost_equal(-a, onp.array([-1, -2, -3.0]))
    assert_almost_equal(abs(mx.np.array([-1.0, 2.0])), onp.array([1, 2.0]))


def test_inplace_ops():
    a = mx.np.ones((3,))
    a += 2
    assert_almost_equal(a, onp.full(3, 3.0))
    a *= 2
    assert_almost_equal(a, onp.full(3, 6.0))
    a -= 1
    a /= 5
    assert_almost_equal(a, onp.full(3, 1.0))


def test_comparison_ops():
    a = mx.np.array([1.0, 2.0, 3.0])
    b = mx.np.array([3.0, 2.0, 1.0])
    assert (a == b).asnumpy().tolist() == [False, True, False]
    assert (a != b).asnumpy().tolist() == [True, False, True]
    assert (a < b).asnumpy().tolist() == [True, False, False]
    assert (a >= b).asnumpy().tolist() == [False, True, True]


def test_indexing():
    x = mx.np.arange(12).reshape(3, 4)
    assert x[1, 2].item() == 6.0
    assert x[1].shape == (4,)
    assert x[:, 1].shape == (3,)
    assert x[1:3].shape == (2, 4)
    assert x[-1, -1].item() == 11.0
    idx = mx.np.array([0, 2], dtype="int32")
    assert x[idx].shape == (2, 4)


def test_setitem():
    x = mx.np.zeros((3, 3))
    x[1, 1] = 5.0
    assert x[1, 1].item() == 5.0
    x[0] = 2.0
    assert_almost_equal(x[0], onp.full(3, 2.0))
    x[:] = 1.0
    assert x.asnumpy().sum() == 9.0


def test_shape_methods():
    x = rand_ndarray((2, 3, 4))
    assert x.reshape(6, 4).shape == (6, 4)
    assert x.reshape((-1,)).shape == (24,)
    assert x.transpose().shape == (4, 3, 2)
    assert x.transpose(1, 0, 2).shape == (3, 2, 4)
    assert x.T.shape == (4, 3, 2)
    assert x.swapaxes(0, 2).shape == (4, 3, 2)
    assert x.expand_dims(0).shape == (1, 2, 3, 4)
    assert x.expand_dims(0).squeeze(0).shape == (2, 3, 4)
    assert x.flatten().shape == (2, 12)
    assert x.ravel().shape == (24,)
    assert x.tile((2, 1, 1)).shape == (4, 3, 4)
    assert x.repeat(2, axis=1).shape == (2, 6, 4)


def test_reduce_methods():
    x = mx.np.array([[1.0, 2.0], [3.0, 4.0]])
    assert x.sum().item() == 10.0
    assert_almost_equal(x.sum(axis=0), onp.array([4.0, 6.0]))
    assert x.mean().item() == 2.5
    assert x.max().item() == 4.0
    assert x.min().item() == 1.0
    assert x.prod().item() == 24.0
    assert x.argmax().item() == 3
    assert x.argmin(axis=1).asnumpy().tolist() == [0, 0]
    assert_almost_equal(x.norm(), onp.sqrt(30.0).astype("float32"))
    assert x.sum(axis=0, keepdims=True).shape == (1, 2)


def test_dtype_cast():
    x = mx.np.array([1.5, 2.5])
    y = x.astype("int32")
    assert y.dtype == onp.int32
    assert y.asnumpy().tolist() == [1, 2]
    z = x.astype("float16")
    assert z.dtype == onp.float16
    b = x.astype("bfloat16")
    assert "bfloat16" in str(b.dtype)


def test_context_placement():
    x = mx.np.ones((2, 2), ctx=mx.cpu())
    assert x.context.device_type == "cpu"
    y = x.as_in_context(mx.cpu(0))
    assert y is x  # same ctx: no copy
    c = x.copy()
    c[0, 0] = 9.0
    assert x[0, 0].item() == 1.0  # copy is deep


def test_accelerator_context_raises_without_accelerator():
    """No quiet CPU stand-in: on a CPU-only backend mx.tpu()/mx.gpu()
    name what jax found instead of resolving to a host device."""
    for ctx in (mx.tpu(), mx.gpu(), mx.Context("tpu", 1)):
        with pytest.raises(mx.MXNetError, match="cpu"):
            ctx.jax_device
    assert mx.current_context().device_type == "cpu"   # auto still works


def test_sync_and_wait():
    x = mx.np.ones((8, 8))
    y = mx.np.dot(x, x)
    y.wait_to_read()
    mx.waitall()
    assert y.asnumpy().sum() == 8 * 8 * 8


def test_scalar_conversions():
    x = mx.np.array([3.5])
    assert float(x) == 3.5
    assert int(mx.np.array([2])) == 2
    assert bool(mx.np.array([1.0]))
    with pytest.raises(ValueError):
        bool(mx.np.ones((2,)))
    assert len(mx.np.ones((5, 2))) == 5
    assert mx.np.array([1.0, 2.0]).tolist() == [1.0, 2.0]


def test_zeros_ones_like():
    x = rand_ndarray((2, 3))
    assert x.zeros_like().asnumpy().sum() == 0
    assert x.ones_like().asnumpy().sum() == 6


def test_concat_stack_split():
    a = mx.np.ones((2, 3))
    b = mx.np.zeros((2, 3))
    c = mx.np.concatenate([a, b], axis=0)
    assert c.shape == (4, 3)
    c2 = mx.nd.concat(a, b, dim=1)
    assert c2.shape == (2, 6)
    s = mx.np.stack([a, b], axis=0)
    assert s.shape == (2, 2, 3)
    parts = mx.np.split(mx.np.arange(10), 2)
    assert len(parts) == 2 and parts[0].shape == (5,)


def test_take_gather():
    x = mx.np.array([[1.0, 2.0], [3.0, 4.0], [5.0, 6.0]])
    t = mx.np.take(x, mx.np.array([0, 2], dtype="int32"), axis=0)
    assert_almost_equal(t, onp.array([[1, 2], [5, 6.0]]))
    oh = mx.npx.one_hot(mx.np.array([0, 2], dtype="int32"), 3)
    assert_almost_equal(oh, onp.array([[1, 0, 0], [0, 0, 1.0]]))


def test_ordering():
    x = mx.np.array([3.0, 1.0, 2.0])
    assert mx.nd.sort(x).asnumpy().tolist() == [1, 2, 3]
    assert mx.nd.sort(x, is_ascend=False).asnumpy().tolist() == [3, 2, 1]
    assert mx.nd.argsort(x).asnumpy().tolist() == [1, 2, 0]
    tk = mx.nd.topk(x, k=2, ret_typ="value")
    assert tk.asnumpy().tolist() == [3, 2]


def test_where_clip():
    x = mx.np.array([-1.0, 0.5, 2.0])
    assert_almost_equal(x.clip(0.0, 1.0), onp.array([0, 0.5, 1.0]))
    w = mx.np.where(x > 0, x, x.zeros_like())
    assert_almost_equal(w, onp.array([0, 0.5, 2.0]))


def test_numpy_interop():
    x = mx.np.ones((2, 2))
    n = onp.asarray(x)
    assert n.sum() == 4.0
    y = mx.np.array(onp.eye(3))
    assert y.shape == (3, 3)


def test_waitall_tracks_arrays():
    from mxnet_tpu import engine
    x = mx.np.ones((4, 4))
    y = x * 2
    assert len(engine._LIVE) > 0
    mx.waitall()


def test_multinomial_get_prob():
    p = mx.np.array([0.1, 0.2, 0.7])
    s, logp = mx.nd.random.multinomial(p, shape=4, get_prob=True)
    assert s.shape == (4,) and logp.shape == (4,)
    probs = onp.array([0.1, 0.2, 0.7])
    expect = onp.log(probs / probs.sum())
    # accelerator libm log deviates at the ~1e-4 level (cross-backend
    # tolerance class, see test_utils.check_consistency)
    from mxnet_tpu.test_utils import default_context
    tol = 1e-3 if default_context().device_type != "cpu" else 1e-5
    for si, lp in zip(s.asnumpy(), logp.asnumpy()):
        assert abs(lp - expect[int(si)]) < tol


def test_norm_ord_high_rank():
    x = mx.np.ones((2, 3, 4))
    assert abs(x.norm(ord=1).item() - 24.0) < 1e-5
    assert abs(x.norm().item() - onp.sqrt(24.0)) < 1e-5


def test_legacy_broadcast_elemwise_aliases():
    """1.x op-name surface: broadcast_*/elemwise_* spellings (reference
    src/operator/tensor/elemwise_binary_broadcast_op*)."""
    a = mx.np.array(onp.arange(6.0).reshape(2, 3).astype("float32"))
    b = mx.np.array(onp.ones((1, 3), dtype="float32"))
    assert onp.allclose(mx.nd.broadcast_add(a, b).asnumpy(),
                        a.asnumpy() + 1)
    assert onp.allclose(mx.nd.broadcast_mul(a, a).asnumpy(),
                        a.asnumpy() ** 2)
    assert onp.allclose(mx.nd.elemwise_sub(a, a).asnumpy(), 0)
    assert mx.nd.broadcast_axis(mx.np.ones((1, 3)), axis=0,
                                size=4).shape == (4, 3)
    assert mx.nd.broadcast_like(mx.np.ones((1, 3)),
                                mx.np.ones((5, 3))).shape == (5, 3)
    assert mx.nd.reshape_like(a, mx.np.ones((3, 2))).shape == (3, 2)
    assert onp.allclose(mx.nd.reverse(a, axis=1).asnumpy(),
                        a.asnumpy()[:, ::-1])
    assert onp.allclose(mx.nd.slice(a, (0, 1), (2, 3)).asnumpy(),
                        a.asnumpy()[0:2, 1:3])
    sm = mx.nd.softmin(a, axis=1).asnumpy()
    assert onp.allclose(sm.sum(axis=1), 1, atol=1e-5)
    m, v = mx.nd.moments(a, axes=(0,))
    assert onp.allclose(m.asnumpy(), a.asnumpy().mean(0))
    assert onp.allclose(v.asnumpy(), a.asnumpy().var(0))
    assert mx.nd.shape_array(a).asnumpy().tolist() == [2, 3]
    assert mx.nd.size_array(a).asnumpy().tolist() == [6]
    assert mx.nd.batch_take(a, mx.np.array(onp.array([2, 0]))) \
        .asnumpy().tolist() == [2.0, 3.0]


def test_spatial_transformer_sampling():
    """grid_generator + bilinear_sampler (reference
    src/operator/{grid_generator,bilinear_sampler}.cc): identity affine
    and zero warp reproduce the input; gradients flow to the data."""
    img = mx.np.array(onp.random.rand(2, 3, 5, 7).astype("float32"))
    theta = mx.np.array(onp.tile(
        onp.array([1, 0, 0, 0, 1, 0], dtype="float32"), (2, 1)))
    grid = mx.nd.grid_generator(theta, "affine", target_shape=(5, 7))
    out = mx.nd.bilinear_sampler(img, grid)
    assert onp.allclose(out.asnumpy(), img.asnumpy(), atol=1e-4)
    flow = mx.np.array(onp.zeros((2, 2, 5, 7), dtype="float32"))
    out2 = mx.nd.bilinear_sampler(img, mx.nd.grid_generator(flow, "warp"))
    assert onp.allclose(out2.asnumpy(), img.asnumpy(), atol=1e-4)
    # translation by a full grid-width pushes samples out of range -> 0
    theta_t = mx.np.array(onp.tile(
        onp.array([1, 0, 2.5, 0, 1, 0], dtype="float32"), (2, 1)))
    out3 = mx.nd.bilinear_sampler(
        img, mx.nd.grid_generator(theta_t, "affine", target_shape=(5, 7)))
    assert (onp.asarray(out3.asnumpy())[:, :, :, -1] == 0).all()
    img.attach_grad()
    with mx.autograd.record():
        s = mx.nd.bilinear_sampler(img, grid).sum()
    s.backward()
    g = img.grad.asnumpy()
    assert onp.isfinite(g).all() and abs(g).sum() > 0
