"""The plain reference against the program's oracle-kernel path, at a tiny
size on the CPU: forward, loss, gradient and inverse."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import synthetic
from bench.lib import weights as W
from bench.reference.glow import Glow


def _model(haar):
    return {"n_scales": 2, "k_steps": 2, "hidden": 8, "channels": 3, "haar": haar,
            "clamp": 2.0, "grad_mode": "coupled", "builder": "repro.core.build_glow_scanned",
            "builder_args": ["n_scales", "k_steps", "hidden", "grad_mode", "haar", "clamp"],
            "init": {"actnorm_std": 0.05, "last_conv_out_std": 0.1}}


def _program_nll(flow, params, x):
    from repro.core.distributions import flatten_state, std_normal_logpdf

    z, ld = flow.forward(params, x)
    return -jnp.mean(std_normal_logpdf(z) + ld) / flatten_state(z).shape[1]


@pytest.mark.parametrize("haar", [True, False], ids=["haar", "squeeze"])
def test_reference_matches_program(haar):
    from repro.kernels.common import reference_kernels

    model = _model(haar)
    key = synthetic.key_from_seed(2**33 + 5)
    w, bufs = W.make(key, model, 8)
    params = W.to_program(w, bufs)
    x = synthetic.images(jax.random.PRNGKey(1), 4, 8)
    flow = W.build_flow(model)
    ref = Glow(model)
    with reference_kernels():
        z_p, ld_p = jax.jit(flow.forward)(params, x)
        loss_p, g_p = jax.jit(jax.value_and_grad(lambda p: _program_nll(flow, p, x),
                                                 allow_int=True))(params)
        x_back = jax.jit(flow.inverse)(params, z_p)
    z_r, ld_r = jax.jit(ref.forward)(w, bufs, x)
    for a, b in zip(z_p, z_r):
        np.testing.assert_allclose(a, b, atol=1e-5)
    np.testing.assert_allclose(ld_p, ld_r, rtol=1e-5, atol=1e-4)
    loss_r, g_r = jax.jit(jax.value_and_grad(lambda v: ref.nll(v, bufs, x)))(w)
    assert float(loss_p) == pytest.approx(float(loss_r), rel=1e-6)
    for gp, gr in zip(W.from_program(g_p), g_r):
        for name in W.FLOAT_LEAVES:
            np.testing.assert_allclose(gp[name], gr[name], rtol=1e-4, atol=1e-6,
                                       err_msg=name)
    np.testing.assert_allclose(jax.jit(ref.inverse)(w, bufs, z_r), x, atol=1e-5)
    np.testing.assert_allclose(x_back, x, atol=1e-5)


def test_weights_have_the_program_layout():
    model = _model(True)
    params = W.to_program(*W.make(jax.random.PRNGKey(0), model, 8))
    W.check_layout(W.build_flow(model), params, jnp.zeros((2, 8, 8, 3)))
    # every flow step's last conditioner conv is non-zero: no coupling is
    # the identity, so the check reaches the conditioner
    for w in W.make(jax.random.PRNGKey(0), model, 8)[0]:
        assert bool(jnp.all(jnp.abs(w["w3"]).sum(axis=(1, 2, 3, 4)) > 0))


def test_seed_uses_all_64_bits():
    a = synthetic.key_from_seed(5)
    b = synthetic.key_from_seed(2**40 + 5)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
