"""perf/reference/gpt2.py against the program's own model, in float32
with ``attn_impl="xla"``, on ``gpt_tiny``; and how far the faults the
training check must catch move the loss."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.reference import gpt2 as reference
from perf.tests.tiny import TINY_CONFIG
from pytorch_multiprocessing_distributed_tpu import models


@pytest.fixture(scope="module")
def tiny():
    model = models.get_model("gpt_tiny", dtype=jnp.float32,
                             attn_impl="xla")
    tokens = np.random.default_rng(0).integers(0, 257, (3, 48),
                                               dtype=np.int32)
    params = model.init(jax.random.PRNGKey(1), tokens)["params"]
    # an untrained model's biases are zero and its LayerNorms the
    # identity scale: perturb them so that every term is exercised
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    params = tree.unflatten([
        leaf + 0.05 * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)])
    return model, params, tokens


def test_logits_agree_with_the_program(tiny):
    model, params, tokens = tiny
    want = np.asarray(model.apply({"params": params}, tokens))
    fn = reference.make_logits_fn(TINY_CONFIG)
    for row in range(tokens.shape[0]):
        got = np.asarray(fn(params, tokens[row]))
        # float32 against float32: only summation order differs
        np.testing.assert_allclose(got, want[row], atol=2e-5, rtol=2e-5)


def test_loss_agrees_with_the_program(tiny):
    from pytorch_multiprocessing_distributed_tpu.ops.losses import (
        cross_entropy_per_sample)

    model, params, tokens = tiny
    logits = model.apply({"params": params}, tokens)[:, :-1]
    want = float(jnp.mean(cross_entropy_per_sample(
        logits.reshape(-1, logits.shape[-1]), tokens[:, 1:].reshape(-1))))
    got = float(reference.make_loss_fn(TINY_CONFIG)(params, tokens))
    assert got == pytest.approx(want, abs=1e-5)


def test_reference_is_causal(tiny):
    _model, params, tokens = tiny
    fn = reference.make_logits_fn(TINY_CONFIG)
    a = np.asarray(fn(params, tokens[0]))
    changed = tokens[0].copy()
    changed[30:] = (changed[30:] + 1) % 257
    b = np.asarray(fn(params, changed))
    np.testing.assert_array_equal(a[:30], b[:30])
    assert np.abs(a[30:] - b[30:]).max() > 1e-3
