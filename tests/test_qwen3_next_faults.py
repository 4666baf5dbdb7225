"""Qwen3-Next's sensitivity tests: what the served comparison of
``tests/test_qwen3_next.py`` (its model, weights, load and helpers, imported
from there) must tell from the sound program at float32: a state taken at the
bucket's end, a seat that keeps the previous stream's rows, and each planted
fault of ``benchmark/control_qwen3next.py``.  A file of its own so that a
test run over several workers (one file a worker) does not wait for one
worker to compile the served path fourteen times."""

import pytest

from deepspeed_tpu.models import qwen3_next
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops import gated_delta as gd
from benchmark import control_qwen3next
from test_qwen3_next import TOL, model_params, serve_and_compare  # noqa: F401


def test_state_taken_at_the_buckets_end_fails(model_params, monkeypatch):
    """The pad after the prompt enters the rule: what this family is most
    likely to get wrong, and the check sees it."""
    _, params = model_params
    monkeypatch.setattr(gd, "mask_pads", lambda g, beta, t_real: (g, beta))
    monkeypatch.setattr(
        qwen3_next, "conv_tail_at",
        lambda padded, t_real, width: padded[:, -width:])
    worst, _, _ = serve_and_compare(params, light=True)
    assert worst > 10 * TOL


def test_a_seat_that_keeps_the_previous_rows_fails(model_params, monkeypatch):
    _, params = model_params
    sound = qwen3_next.Qwen3Next.prefill_paged

    def keeps_rows(self, params, toks, pool, blocks, slot, t_real):
        row, new = sound(self, params, toks, pool, blocks, slot, t_real)
        return row, dict(new, conv=pool["conv"], delta=pool["delta"])
    monkeypatch.setattr(qwen3_next.Qwen3Next, "prefill_paged", keeps_rows)
    worst, _, _ = serve_and_compare(params, light=True)
    assert worst > 10 * TOL


# what each fault must read at the least: a mechanism computed otherwise
# stands ten times over TOL; a state rounded to bfloat16 moves a logit by
# bfloat16's rounding and stands thirty times over the sound program's reading
FAULT_FLOORS = dict.fromkeys(control_qwen3next.FAULTS, 10 * TOL)
FAULT_FLOORS.update(state_bf16=TOL / 10)


@pytest.fixture(scope="module")
def sound_reading(model_params):
    return serve_and_compare(model_params[1], light=True)[0]


@pytest.mark.parametrize("fault", control_qwen3next.FAULTS)
def test_each_planted_fault_fails_at_float32(model_params, sound_reading,
                                             fault):
    """``benchmark/control_qwen3next.py``'s faults, each against the sound
    reference at float32, where nothing hides below the precision served."""
    _, params = model_params
    owners = (qwen3_next, qwen3_next.Qwen3Next, gd, dropless)
    before = [dict(vars(o)) for o in owners]
    unplant = control_qwen3next.plant(fault)
    try:
        worst, _, _ = serve_and_compare(params, light=True)
    finally:
        unplant()
    assert [dict(vars(o)) for o in owners] == before    # and it is out again
    assert sound_reading < TOL / 30
    assert worst > max(FAULT_FLOORS[fault], 30 * sound_reading), (fault, worst)
