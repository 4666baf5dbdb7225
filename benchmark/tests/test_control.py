"""The control of the comparison that decides ``correct`` (PERF.md, "the
control"): the reference with every weight matrix rounded to 8 bits, in the
program's place, reads several times what the program itself reads against
the float32 reference.  At gpt2-tiny on the CPU; the limits of the cells were
set from the same two readings at the cells' own sizes on the chip."""

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import control, harness, serving
from benchmark.tests.tiny import TINY


@pytest.fixture(scope="module")
def setting():
    model = harness.build_model(TINY, jnp.bfloat16, embd_pdrop=0.0,
                                attn_pdrop=0.0, resid_pdrop=0.0,
                                attention_impl="jnp")
    params = harness.seeded_weights(model, 2 ** 31 + 9, jnp.bfloat16)
    tokens = jnp.asarray(np.random.default_rng(5).integers(
        0, 1024, size=(3, 129)), jnp.int32)
    return model, params, tokens


@pytest.mark.parametrize("precision, stat, apart", [
    ("float8_e4m3fn", 0, 3.0), ("float8_e4m3fn", 1, 3.0),
    # int8 with a scale a channel keeps 7 bits to bfloat16's 8: only the
    # root mean square, steady from seed to seed, tells it from the program
    ("int8", 1, 1.5)])
def test_the_control_reads_above_the_program(setting, precision, stat, apart):
    """``stat`` 0: the largest difference over the largest reference logit
    (``logit_tol``); 1: root mean square over root mean square
    (``logit_rms_tol``)."""
    model, params, tokens = setting
    reference = harness.reference(TINY)
    last = jnp.array([127, 90, 40], jnp.int32)
    ref = np.asarray(reference.logits_at(TINY, params, tokens[:, :-1], last))
    ours = np.asarray(model.apply(params, tokens[:, :-1]), np.float32)[
        np.arange(3), np.asarray(last)]
    sound = serving.logit_errors(ours, ref)[stat]
    got = control.logit_error(
        TINY, reference, params, control.coarser(params, precision),
        tokens[:, :-1], last)[stat]
    print(f"{precision} stat {stat}: program {sound:.3e}, control {got:.3e}")
    assert got > apart * sound, (got, sound)
    # NOT asserted for the training cells' number: at these random weights
    # the first batch's loss sits at ln(vocabulary) whatever the weights'
    # precision (program 1.35e-05, control 1.44e-05 of the reference's loss
    # here), so ``loss_rtol`` cannot tell the control from the program:
    # PERF.md, Open questions.


def test_coarser_leaves_vectors_and_types_alone(setting):
    _, params, _ = setting
    rough = control.coarser(params)
    assert rough["lnf_scale"].dtype == params["lnf_scale"].dtype
    assert jnp.array_equal(rough["lnf_scale"], params["lnf_scale"])
    assert rough["wte"].dtype == params["wte"].dtype
    assert not jnp.array_equal(rough["wte"], params["wte"])
    # 8 bits: at most 256 distinct values in the whole matrix
    assert len(np.unique(np.asarray(rough["wte"].astype(jnp.float32)))) <= 256
    # int8: at most 255 levels in each output channel (a column)
    column = np.asarray(control.coarser(params, "int8")["wte"][:, 3],
                        np.float32)
    assert len(np.unique(column)) <= 255
    assert not jnp.array_equal(column, params["wte"][:, 3])
