"""The exact GELU (``npx.gelu``, ``approximate=False``): its values are
``jax.nn.gelu``'s bit for bit, and its own derivative rule gives
autodiff's gradient, eagerly, under ``autograd.record`` and in a
hybridized block; the tanh form is ``jax.nn.gelu``'s untouched."""
import numpy as onp
import jax
import jax.numpy as jnp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import npx
from mxnet_tpu.gluon import nn
from mxnet_tpu.ndarray.ndarray import from_jax

# gelu'(x) = 0 at x = -0.75179...: the grid holds it and both ends
_ROOT = -0.7517915241
_DTYPES = [jnp.float32, jnp.bfloat16]


def _grid(dtype):
    x = onp.concatenate([onp.linspace(-8.0, 8.0, 4001), [_ROOT, 0.0]])
    return jnp.asarray(x, jnp.float32).astype(dtype)


def _head(x):
    """A cotangent of varied sign and size."""
    g = onp.cos(onp.arange(x.shape[0]) * 0.37) * 3.0
    return jnp.asarray(g, jnp.float32).astype(x.dtype)


def _bits(a):
    a = onp.asarray(a)
    return a.view(onp.uint16 if a.dtype.itemsize == 2 else onp.uint32)


def _want_grad(x, g):
    """Autodiff of ``jax.nn.gelu`` at ``x``, in float32."""
    f = lambda v: jax.nn.gelu(v, approximate=False)     # noqa: E731
    return jax.vjp(f, x.astype(jnp.float32))[1](g.astype(jnp.float32))[0]


def _recorded(block, x, g):
    """Value and input gradient of ``block`` under autograd.record."""
    a = from_jax(x)
    a.attach_grad()
    with mx.autograd.record():
        y = block(a)
    y.backward(from_jax(g))
    return y._data, a.grad._data


class _Gelu(mx.gluon.HybridBlock):
    def __init__(self, approximate):
        super().__init__()
        self._approx = approximate

    def forward(self, x):
        return npx.gelu(x, approximate=self._approx)


def _block(hybrid, approximate=False):
    b = _Gelu(approximate)
    if hybrid:
        b.hybridize()
    return b


def _exact(x):
    """``jax.nn.gelu`` as a compiled program computes it: what the op
    gave before its derivative rule in every mode (an eager, op-by-op
    ``jax.nn.gelu`` rounds the erfc's bfloat16 argument, which XLA on
    the CPU leaves unrounded in a compiled one)."""
    return jax.jit(lambda v: jax.nn.gelu(v, approximate=False))(x)


@pytest.mark.parametrize("mode", ["eager", "recorded", "hybridized",
                                  "hybridized_recorded"])
@pytest.mark.parametrize("dtype", _DTYPES)
def test_gelu_values_are_jax_nn_gelus_bit_for_bit(dtype, mode):
    x = _grid(dtype)
    block = _block(mode.startswith("hybridized"))
    if mode.endswith("recorded"):
        y, _ = _recorded(block, x, _head(x))
    else:
        y = block(from_jax(x))._data
    assert y.dtype == x.dtype
    assert (_bits(y) == _bits(_exact(x))).all()


@pytest.mark.parametrize("hybrid", [False, True])
def test_gelu_gradient_is_autodiffs_to_float32_rounding(hybrid):
    x = _grid(jnp.float32)
    g = _head(x)
    _, got = _recorded(_block(hybrid), x, g)
    want = _want_grad(x, g)
    err = float(jnp.abs(got - want).max())
    assert err <= 1e-6 * float(jnp.abs(g).max()), err


@pytest.mark.parametrize("hybrid", [False, True])
def test_gelu_gradient_in_bfloat16_is_one_rounding_of_the_float32_one(
        hybrid):
    """bfloat16 inputs: the gradient is ``g·gelu'(x)`` formed in float32
    and rounded once; the erfc's argument is rounded as the value's is,
    which moves ``gelu'`` by at most a bfloat16 step of ``g``."""
    x = _grid(jnp.bfloat16)
    g = _head(x)
    _, got = _recorded(_block(hybrid), x, g)
    assert got.dtype == jnp.bfloat16
    want = _want_grad(x, g)
    err = jnp.abs(got.astype(jnp.float32) - want)
    bound = 2.0 ** -8 * (jnp.abs(want) + jnp.abs(g.astype(jnp.float32)))
    assert bool((err <= bound).all()), float((err / bound).max())


def test_gelu_forward_mode_through_the_op():
    x = _grid(jnp.float32)
    t = _head(x)

    def f(v):
        return npx.gelu(from_jax(v))._data

    y, dy = jax.jvp(f, (x,), (t,))
    assert (_bits(y) == _bits(_exact(x))).all()
    want = _want_grad(x, t)
    assert float(jnp.abs(dy - want).max()) <= 1e-6 * float(
        jnp.abs(t).max())
    small = x[::500]
    jac = jax.jacfwd(f)(small)
    want_jac = jax.jacfwd(lambda v: jax.nn.gelu(v, approximate=False))(small)
    onp.testing.assert_allclose(onp.asarray(jac), onp.asarray(want_jac),
                                atol=1e-6)


@pytest.mark.parametrize("hybrid", [False, True])
@pytest.mark.parametrize("dtype", _DTYPES)
def test_gelu_tanh_form_is_jax_nn_gelus(dtype, hybrid):
    x = _grid(dtype)
    g = _head(x)
    y, got = _recorded(_block(hybrid, approximate=True), x, g)
    f = lambda v: jax.nn.gelu(v, approximate=True)      # noqa: E731
    assert (_bits(y) == _bits(jax.jit(f)(x))).all()
    # XLA's CPU tanh differs between a compiled and an op-by-op program;
    # the erf form's gradient differs from this one by ~1e-3 a unit g
    bf16 = dtype == jnp.bfloat16
    onp.testing.assert_allclose(
        onp.asarray(got, onp.float32),
        onp.asarray(jax.vjp(f, x)[1](g)[0], onp.float32),
        rtol=2.0 ** -7 if bf16 else 0.0,
        atol=1e-2 if bf16 else 1e-5 * float(jnp.abs(g).max()))


def test_evaluation_without_differentiation_runs_the_primal_program():
    """No derivative is formed and nothing is held back from fusion when
    nothing is differentiated."""
    from mxnet_tpu.ops.nn import _gelu
    x = jax.ShapeDtypeStruct((64, 4096), jnp.bfloat16)
    text = jax.jit(_gelu).lower(x).as_text()
    assert "optimization_barrier" not in text
    assert text.count("erfc") == jax.jit(
        lambda v: jax.nn.gelu(v, approximate=False)).lower(x).as_text(
        ).count("erfc")
    grad_text = jax.jit(jax.grad(lambda v: _gelu(v).astype(
        jnp.float32).sum())).lower(x).as_text()
    assert "optimization_barrier" in grad_text


@pytest.mark.parametrize("call", [
    lambda a: npx.activation(a, act_type="gelu"),
    lambda a: npx.leaky_relu(a, act_type="gelu"),
    lambda a: nn.Activation("gelu")(a),
    lambda a: nn.GELU()(a),
], ids=["activation", "leaky_relu", "Activation", "GELU"])
def test_every_gelu_name_is_the_exact_gelu(call):
    """MXNet's ``Activation``/``LeakyReLU`` act_type "gelu" is the erf
    form; all four names run the one exact GELU, gradient included."""
    x = _grid(jnp.float32)
    g = _head(x)
    y, got = _recorded(call, x, g)
    assert (_bits(y) == _bits(_exact(x))).all()
    assert float(jnp.abs(got - _want_grad(x, g)).max()) <= 1e-6 * float(
        jnp.abs(g).max())
