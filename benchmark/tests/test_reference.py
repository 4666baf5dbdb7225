"""The plain reference against the program's own forward and loss at
gpt2-tiny on the CPU, in float32: two independent writings of one block."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness
from benchmark.reference import gpt2 as reference
from benchmark.tests.tiny import TINY



def test_logits_and_loss_match_the_program():
    model = harness.build_model(TINY, jnp.float32, embd_pdrop=0.0,
                                attn_pdrop=0.0, resid_pdrop=0.0,
                                attention_impl="jnp")
    params = harness.seeded_weights(model, 2 ** 31 + 5)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 1024, size=(2, 65)), jnp.int32)

    ours = model.apply(params, tokens[:, :-1])
    last = jnp.array([63, 40], jnp.int32)
    ref = reference.logits_at(TINY, params, tokens[:, :-1], last)
    np.testing.assert_allclose(ref, ours[jnp.arange(2), last],
                               rtol=2e-4, atol=2e-4)

    # right padding cannot reach an earlier position
    padded = tokens[:, :-1].at[1, 41:].set(0)
    np.testing.assert_allclose(
        reference.logits_at(TINY, params, padded, last)[1], ref[1],
        rtol=1e-5, atol=1e-5)

    logp = jax.nn.log_softmax(ours.astype(jnp.float32), axis=-1)
    want = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1).mean()
    np.testing.assert_allclose(reference.loss(TINY, params, tokens), want,
                               rtol=1e-5)


def test_weights_follow_the_seed():
    model = harness.build_model(TINY, jnp.float32)
    a = harness.seeded_weights(model, 11, jnp.bfloat16)
    b = harness.seeded_weights(model, 11, jnp.bfloat16)
    c = harness.seeded_weights(model, 12, jnp.bfloat16)
    assert a["wte"].dtype == jnp.bfloat16
    assert jnp.array_equal(a["wte"], b["wte"])
    assert not jnp.array_equal(a["wte"], c["wte"])
    assert 0 <= harness.key_seed(2 ** 31 + 99) < 2 ** 31
