"""The plain reference against the program's model at a tiny size on the
CPU, in float32: the two must agree to rounding, or one of them is not
the block the configuration files describe."""

import jax
import jax.numpy as jnp
import pytest

from benchmark.reference import gpt as reference


@pytest.fixture(scope="module")
def tiny():
    from ray_tpu.models.gpt import GPTConfig, init_params
    cfg = GPTConfig.tiny(dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(3))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 65), 0,
                              cfg.vocab_size)
    return cfg, params, toks[:, :-1], toks[:, 1:]


def test_logits(tiny):
    from ray_tpu.models.gpt import forward
    cfg, params, tokens, _ = tiny
    want, _aux = forward(params, tokens, cfg)
    got = reference.logits_last(params, tokens, 64)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    last = reference.logits_last(params, tokens, 3)
    assert float(jnp.max(jnp.abs(last - want[:, -3:]))) < 1e-5


def test_loss_and_gradient_norm(tiny):
    import optax

    from ray_tpu.models.gpt import loss_fn
    cfg, params, tokens, targets = tiny
    batch = {"tokens": tokens, "targets": targets}
    want = float(loss_fn(params, batch, cfg))
    grads = jax.grad(lambda p: loss_fn(p, batch, cfg))(params)
    loss, gnorm = reference.loss_and_grad_norm(params, tokens, targets,
                                               chunk=2)
    assert loss == pytest.approx(want, rel=1e-6)
    assert gnorm == pytest.approx(float(optax.global_norm(grads)),
                                  rel=1e-5)


def test_reference_imports_nothing_of_the_program():
    import inspect
    src = inspect.getsource(reference)
    assert "import ray_tpu" not in src and "from ray_tpu" not in src
