"""Layer-norm Pallas kernel (ops/pallas/layer_norm.py) in interpret
mode: forward and custom-vjp backward against _ln_ref, and the routing
of row counts the kernel cannot tile. Fast lane (the attention kernel
sweeps in test_pallas_attention.py are slow-lane)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import pallas
from paddle_tpu.ops.pallas import attention as fa


@pytest.fixture(autouse=True)
def _interpret():
    fa.force_interpret(True)
    yield
    fa.force_interpret(False)


def _ln_inputs(n, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (n, d)) * 3.0 + 1.0,
            jax.random.normal(ks[1], (d,)),
            jax.random.normal(ks[2], (d,)))


@pytest.mark.parametrize("n", [256, 16])
def test_layer_norm_kernel_matches_reference(n):
    from paddle_tpu.ops.pallas import layer_norm as ln

    x, s, b = _ln_inputs(n, 512)
    assert ln.usable(n, 512)
    np.testing.assert_allclose(
        np.asarray(ln.layer_norm(x, s, b, 1e-5)),
        np.asarray(ln._ln_ref(x, s, b, 1e-5)), atol=2e-5, rtol=2e-5)

    def loss(f):
        return lambda *a: jnp.sum(jnp.sin(f(*a, 1e-5)))

    got = jax.grad(loss(ln.layer_norm), argnums=(0, 1, 2))(x, s, b)
    want = jax.grad(loss(ln._ln_ref), argnums=(0, 1, 2))(x, s, b)
    for g, w, name in zip(got, want, ("x", "scale", "bias")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=1e-4, rtol=1e-4,
                                   err_msg=f"d{name}")


def test_layer_norm_serving_rows_take_the_reference():
    """A serve program normalizes n_slots + 1 rows (9 at eight lanes):
    not a multiple of the fp32 sublane, so the op must route them to
    _ln_ref (and say so) instead of tiling a (1, d) block."""
    import paddle_tpu as fluid
    from paddle_tpu.core.program import Operator
    from paddle_tpu.core.registry import run_op
    from paddle_tpu.ops.pallas import layer_norm as ln

    assert not ln.usable(9, 512)
    x, s, b = _ln_inputs(9, 512, seed=1)
    block = fluid.Program().global_block
    op = Operator(block, "layer_norm",
                  {"X": ["x"], "Scale": ["s"], "Bias": ["b"]},
                  {"Y": ["y"], "Mean": ["m"], "Variance": ["v"]},
                  {"epsilon": 1e-5, "begin_norm_axis": 1})
    env = {"x": x, "s": s, "b": b}
    with pallas.record_routes() as routes:
        run_op(op, env)
    assert routes == [("layer_norm", (9, 512), False)]
    np.testing.assert_allclose(
        np.asarray(env["y"]), np.asarray(ln._ln_ref(x, s, b, 1e-5)),
        atol=1e-6, rtol=1e-6)
