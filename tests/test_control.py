"""Backprop-through-ODE formation control (paper supplementary material)."""
import jax
import jax.numpy as jnp
import pytest

from repro.core.orbital import ClusterDesign, ControlProblem, rollout, train_controller
from repro.core.orbital.control import init_policy, policy_apply

pytestmark = pytest.mark.usefixtures("x64")


@pytest.fixture(scope="module")
def trained(x64):
    d = ClusterDesign(n_side=3, spacing=100.0)
    prob = ControlProblem(design=d, u_max=2e-5, control_dt=60.0, substeps=4,
                          dv_weight=1e3)
    params, info = train_controller(prob, n_intervals=20, iters=25, lr=3e-2,
                                    perturb_scale=8.0)
    return prob, params, info


def test_gradients_flow_through_ode(trained):
    """Reverse-mode AD through the dopri5 rollout produces finite grads."""
    prob, params, info = trained
    g = jax.grad(lambda p: rollout(p, prob, info["y0"], 0.0, 5)[0])(params)
    leaves = jax.tree.leaves(g)
    assert all(bool(jnp.all(jnp.isfinite(l))) for l in leaves)
    assert any(float(jnp.max(jnp.abs(l))) > 0 for l in leaves)


def test_training_reduces_loss(trained):
    _, _, info = trained
    h = info["loss_history"]
    assert h[-1] < 0.6 * h[0]


def test_controller_beats_free_fall(trained):
    prob, params, info = trained
    zero = jax.tree.map(jnp.zeros_like, init_policy(jax.random.PRNGKey(0)))
    _, d_off = rollout(zero, prob, info["y0"], 0.0, 20)
    _, d_on = rollout(params, prob, info["y0"], 0.0, 20)
    assert float(d_on["rms_pos_err"]) < 0.8 * float(d_off["rms_pos_err"])


def test_thrust_respects_authority_limit():
    params = init_policy(jax.random.PRNGKey(1))
    err = 1e3 * jax.random.normal(jax.random.PRNGKey(2), (17, 6))
    u = policy_apply(params, err, u_max=2e-5)
    assert float(jnp.max(jnp.abs(u))) <= 2e-5 + 1e-12
