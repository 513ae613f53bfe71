"""models.attention.Dropout: flax's dropout with the keep-mask stored.

The module only adds an optimization barrier on the mask, so every
output, gradient and parameter path must be bit-identical to
``flax.linen.Dropout`` under the same rng.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lddl_tpu.models import attention, bart, bert
from lddl_tpu.models import (BartConfig, BartForPreTraining, BertConfig,
                             BertForPreTraining)
from lddl_tpu.models.attention import Dropout

KEY = jax.random.PRNGKey(7)


def _assert_bit_identical(a, b):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _out_and_vjp(cls, attrs, x, cotangent, deterministic):
    module = cls(**attrs)

    def f(x):
        return module.apply({}, x, deterministic=deterministic,
                            rngs={"dropout": KEY})

    out, vjp = jax.vjp(jax.jit(f), x)
    return out, vjp(cotangent)


@pytest.mark.parametrize("attrs,deterministic", [
    ({"rate": 0.1}, False),
    ({"rate": 0.0}, False),
    ({"rate": 1.0}, False),
    ({"rate": 0.1}, True),
    ({"rate": 0.3, "broadcast_dims": (1,)}, False),
], ids=["rate0.1", "rate0", "rate1", "deterministic", "broadcast_dims"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dropout_bit_identical_to_flax(attrs, deterministic, dtype):
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 5, 16, 16), dtype)
    g = jax.random.normal(jax.random.PRNGKey(2), x.shape, dtype)
    ours = _out_and_vjp(Dropout, attrs, x, g, deterministic)
    theirs = _out_and_vjp(nn.Dropout, attrs, x, g, deterministic)
    _assert_bit_identical(ours, theirs)
    out = np.asarray(ours[0])
    if deterministic or attrs["rate"] == 0.0:
        _assert_bit_identical(ours[0], x)
    elif attrs["rate"] == 1.0:
        assert not out.any()
    else:
        dropped = out == 0
        assert dropped.any() and not dropped.all()
        if attrs.get("broadcast_dims"):
            # One mask shared along the broadcast dim.
            assert (dropped == dropped[:, :1]).all()


def test_dropout_sites_keep_flax_names():
    class Two(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = Dropout(0.1)(x, deterministic=False)
            return Dropout(0.1)(x, deterministic=False)

    _, state = Two().apply({}, jnp.ones((2, 4)), rngs={"dropout": KEY},
                           capture_intermediates=True,
                           mutable=["intermediates"])
    assert sorted(state["intermediates"]) == [
        "Dropout_0", "Dropout_1", "__call__"]


def _bert_run():
    cfg = BertConfig.tiny(dropout_rng_impl="threefry",
                          attention_impl="dense")
    model = BertForPreTraining(cfg)
    B, L = 2, 32
    ids = jax.random.randint(jax.random.PRNGKey(3), (B, L), 0,
                             cfg.vocab_size)
    mask = jnp.ones_like(ids).at[1, L - 5:].set(0)
    return model, (ids, jnp.zeros_like(ids), mask)


def _bart_run():
    cfg = BartConfig.tiny(dropout_rng_impl="threefry",
                          attention_impl="dense")
    model = BartForPreTraining(cfg)
    B, L = 2, 32
    ids = jax.random.randint(jax.random.PRNGKey(3), (B, L), 0,
                             cfg.vocab_size)
    mask = jnp.ones_like(ids).at[1, L - 5:].set(0)
    return model, (ids, mask, jnp.roll(ids, 1, axis=1))


def _train_outputs(make, dropout_cls, monkeypatch):
    """(params, dropout logits, gradients, deterministic logits) with
    ``dropout_cls`` at every dropout site of the model."""
    for module in (attention, bert, bart):
        monkeypatch.setattr(module, "Dropout", dropout_cls)
    model, inputs = make()
    params = model.init(jax.random.PRNGKey(0), *inputs)

    def loss(p):
        out = model.apply(p, *inputs, deterministic=False,
                          rngs={"dropout": KEY})
        total = sum(o.astype(jnp.float32).sum() for o in jax.tree.leaves(out))
        return total, out

    (_, logits), grads = jax.jit(
        jax.value_and_grad(loss, has_aux=True))(params)
    plain = model.apply(params, *inputs, deterministic=True)
    return params, logits, grads, plain


@pytest.mark.parametrize("make", [_bert_run, _bart_run],
                         ids=["bert", "bart"])
def test_model_bit_identical_to_flax_dropout(make, monkeypatch):
    with monkeypatch.context() as mp:
        params, logits, grads, plain = _train_outputs(make, Dropout, mp)
    with monkeypatch.context() as mp:
        theirs = _train_outputs(make, nn.Dropout, mp)
    # Same parameter tree (paths and values), logits and gradients.
    _assert_bit_identical(params, theirs[0])
    _assert_bit_identical(logits, theirs[1])
    _assert_bit_identical(grads, theirs[2])
    # Dropout engaged: the deterministic forward reads otherwise.
    first = np.asarray(jax.tree.leaves(logits)[0])
    assert not np.array_equal(first, np.asarray(jax.tree.leaves(plain)[0]))
