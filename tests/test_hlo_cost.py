"""Validate the trip-count-aware HLO cost model against known workloads."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.hlo_cost import HloCostModel, cost_from_compiled, shape_bytes


def test_shape_bytes():
    assert shape_bytes("f32[64,512]{1,0}") == 64 * 512 * 4
    assert shape_bytes("bf16[2,3]") == 12
    assert shape_bytes("(f32[4], s32[2])") == 24
    assert shape_bytes("pred[]") == 1


def _compiled(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_scan_flops_multiplied_by_trip_count():
    def f(w, x):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        y, _ = jax.lax.scan(body, x, w)
        return y.sum()

    w = jax.ShapeDtypeStruct((8, 512, 512), jnp.float32)
    x = jax.ShapeDtypeStruct((64, 512), jnp.float32)
    cost = cost_from_compiled(_compiled(f, w, x))
    expected = 8 * 2 * 64 * 512 * 512
    assert cost.flops == pytest.approx(expected, rel=0.05), cost.flops


def test_unrolled_matches_xla_cost_analysis():
    def f(w, x):
        c = x
        for i in range(4):
            c = c @ w[i]
        return c.sum()

    w = jax.ShapeDtypeStruct((4, 256, 256), jnp.float32)
    x = jax.ShapeDtypeStruct((32, 256), jnp.float32)
    compiled = _compiled(f, w, x)
    ours = cost_from_compiled(compiled)
    xla = compiled.cost_analysis()
    assert ours.flops == pytest.approx(float(xla["flops"]), rel=0.05)


def test_nested_scan():
    def f(w, x):
        def outer(c, wi):
            def inner(ci, _):
                return jnp.dot(ci, wi), None
            ci, _ = jax.lax.scan(inner, c, None, length=3)
            return ci, None
        y, _ = jax.lax.scan(outer, x, w)
        return y.sum()

    w = jax.ShapeDtypeStruct((5, 128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((16, 128), jnp.float32)
    cost = cost_from_compiled(_compiled(f, w, x))
    expected = 5 * 3 * 2 * 16 * 128 * 128
    assert cost.flops == pytest.approx(expected, rel=0.1), cost.flops


def test_collectives_counted_with_trips():
    import os
    # use whatever devices exist; single-device psum still emits all-reduce?
    # Instead verify on a 2-device reshaped mesh only if available.
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >=2 devices (covered by dry-run otherwise)")


def test_gqa_model_cost_sane():
    """Whole-model train step: walker flops within 2x of analytic 6ND."""
    from repro.configs import get_config
    from repro.models.model_zoo import build_model
    from repro.runtime.train_loop import TrainConfig, make_train_step
    from repro.optim.adamw import adamw_init

    cfg = get_config("qwen1.5-0.5b", smoke=True)
    model = build_model(cfg)
    params_like = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt_like = jax.eval_shape(adamw_init, params_like)
    b, s = 4, 64
    batch = {
        "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
        "labels": jax.ShapeDtypeStruct((b, s), jnp.int32),
    }
    tc = TrainConfig(remat=False, n_loss_chunks=4)
    step = make_train_step(model, tc)
    compiled = (
        jax.jit(step)
        .lower(params_like, opt_like, None, batch, jax.ShapeDtypeStruct((), jnp.int32))
        .compile()
    )
    cost = cost_from_compiled(compiled)
    n = cfg.param_count()
    analytic = 6.0 * n * b * s
    # walker must be the right order of magnitude AND >= fwd+bwd matmul cost
    assert cost.flops > 0.5 * analytic, (cost.flops, analytic)
    assert cost.flops < 4.0 * analytic, (cost.flops, analytic)
