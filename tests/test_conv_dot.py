"""MXNET_CONV_DOT_1X1 path: 1x1 channels-last convs as explicit dots.

The dot lowering (ops/nn.py _conv1x1_cl) must be numerically identical to
the lax.conv_general_dilated path for forward and both gradients, for
stride 1 and strided (projection-shortcut) shapes, including odd spatial
sizes where the strided scatter-back needs trailing pad.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu.ops.nn import convolution


def _attrs(stride):
    return {"kernel": (1, 1), "stride": stride, "dilate": (), "pad": (),
            "num_filter": 5, "num_group": 1, "no_bias": True,
            "layout": "NHWC"}


@pytest.mark.parametrize("stride", [(1, 1), (2, 2)])
@pytest.mark.parametrize("h", [8, 9])
def test_conv1x1_dot_matches_native(monkeypatch, stride, h):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, h, h, 6)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((5, 1, 1, 6)), jnp.float32)
    attrs = _attrs(stride)

    def run(flag):
        monkeypatch.setenv("MXNET_CONV_DOT_1X1", flag)
        y = convolution(attrs, x, w)
        gx, gw = jax.grad(
            lambda x_, w_: jnp.sum(jnp.tanh(convolution(attrs, x_, w_))),
            argnums=(0, 1))(x, w)
        return y, gx, gw

    y_dot, gx_dot, gw_dot = run("1")
    y_nat, gx_nat, gw_nat = run("0")
    np.testing.assert_allclose(y_dot, y_nat, atol=1e-5)
    np.testing.assert_allclose(gx_dot, gx_nat, atol=1e-4)
    np.testing.assert_allclose(gw_dot, gw_nat, atol=1e-4)


def test_conv1x1_pallas_fused_bwd_matches_native(monkeypatch):
    """MXNET_CONV1X1_FUSED_BWD (Pallas dgrad+wgrad single-pass kernel,
    interpret mode off-TPU) must be numerically identical to the native
    path.  Slower on v5e in an earlier chip record, since deleted — kept off by
    default as a documented experiment."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((4, 8, 8, 6)), jnp.float32)  # R=256
    w = jnp.asarray(rng.standard_normal((5, 1, 1, 6)), jnp.float32)
    attrs = _attrs((1, 1))

    def run(flag):
        monkeypatch.setenv("MXNET_CONV1X1_FUSED_BWD", flag)
        y = convolution(attrs, x, w)
        g = jax.grad(
            lambda x_, w_: jnp.sum(jnp.tanh(convolution(attrs, x_, w_))),
            argnums=(0, 1))(x, w)
        return y, g

    y1, g1 = run("1")
    y0, g0 = run("0")
    np.testing.assert_allclose(y1, y0, atol=1e-5)
    np.testing.assert_allclose(g1[0], g0[0], atol=1e-4)
    np.testing.assert_allclose(g1[1], g0[1], atol=1e-4)


def test_conv1x1_dot_under_jit_and_symbol(monkeypatch):
    # the eligibility gate must hold inside jit tracing (shapes abstract)
    monkeypatch.setenv("MXNET_CONV_DOT_1X1", "1")
    import mxnet_tpu as mx
    data = mx.sym.Variable("data")
    out = mx.sym.Convolution(data, num_filter=4, kernel=(1, 1),
                             stride=(2, 2), pad=(0, 0), no_bias=True,
                             layout="NHWC", name="c")
    ex = out.simple_bind(mx.cpu(), data=(2, 5, 5, 3))
    rng = np.random.default_rng(1)
    ex.arg_dict["data"][:] = rng.standard_normal((2, 5, 5, 3))
    ex.arg_dict["c_weight"][:] = rng.standard_normal((4, 1, 1, 3))
    (y,) = ex.forward(is_train=True)
    assert y.shape == (2, 3, 3, 4)
    ex.backward()
    assert ex.grad_dict["c_weight"].shape == (4, 1, 1, 3)
