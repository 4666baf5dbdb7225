"""Traffic kind ``serve_backlog_recurrent``: ``serve_backlog_routed`` (its
backlog, its window, its rows, its ``serve_tokens_per_s`` and its
route-before-logits check, number for number: this module runs ITS ``run`` and
ITS ``check``) for a model whose serving state holds RECURRENT ROWS at a
precision the configuration states (``docs/serving.md#recurrent-state``).

``serve_backlog_routed``'s check compares the logits of a decode step a few
steps after the prefill.  They do not tell at what precision the recurrent
state is KEPT: a state rounded to bfloat16 at every write reads 0.0206 there,
inside the sound program's 0.0197 to 0.0388 (PERF.md section 6, PR 42; the
rounding of a write is 2^-9 of an element, under the bfloat16 of the matmuls
that feed it, and three steps add up little of it).  Yet in
``serve_longanswer_nemotron3`` that state is half of a decode step's bytes: a
program that kept it in fewer bits would halve them and stay ``correct``.
So this check asks the stated precision of the rows themselves,
exactly and with no limit to tune, BEFORE the routed check (whose last act
drops the pool): the check's prompts are seated, ``steps`` decode steps run,
and of each leaf that ``check.state.leaves`` names

* the dtype is ``check.state.dtype``;
* at least ``check.state.fine_share_min`` of the non-zero elements are NOT
  representable in ``check.state.coarser`` (the next precision below): they
  have a bit set among the mantissa bits that ``coarser`` lacks.  An element
  computed in float32 has one in all but 2^-16 of cases, one that went
  through bfloat16 anywhere between the update and the pool has none.  Rows
  never seated are zero and count for nothing.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp

from benchmark import harness, serving

_routed = harness.load_plugin("runners", "serve_backlog_routed")
backlog = _routed.backlog
compare = _routed.compare
route_ids = _routed.route_ids


@functools.partial(jax.jit, static_argnums=1)
def _census(leaf, low_bits):
    """(non-zero elements, those with a bit set among the ``low_bits`` lowest
    of their float32 pattern) of one leaf, counted on the device.  The bits
    are read as integers: a round trip ``x.astype(coarser).astype(float32)
    != x`` is no test on the chip, where XLA drops the pair of converts
    (``xla_allow_excess_precision``) and every element reads coarse."""
    bits = jax.lax.bitcast_convert_type(leaf.astype(jnp.float32), jnp.uint32)
    nonzero = (bits << 1) != 0                      # either zero's sign aside
    fine = nonzero & ((bits & ((1 << low_bits) - 1)) != 0)
    # rows counted in int32, the rows' counts added in float32: a count past
    # 2^31 elements must not wrap, a running float32 sum of ones stops at
    # 2^24, and a share needs no more than seven digits
    total = lambda m: m.sum(-1, dtype=jnp.int32).astype(jnp.float32).sum()
    return total(nonzero), total(fine)


def state_precision(spec, pool):
    """``(ok, facts)`` of the recurrent rows in ``pool`` against
    ``spec = check.state``, as the module docstring sets out."""
    # the mantissa bits float32 has and ``coarser`` has not (bfloat16: 16)
    low_bits = jnp.finfo(jnp.float32).nmant - jnp.finfo(spec["coarser"]).nmant
    facts, ok = {}, True
    for name in spec["leaves"]:
        leaf = pool[name]
        nonzero, fine = (float(n) for n in _census(leaf, int(low_bits)))
        share = fine / nonzero if nonzero else 0.0
        facts[name] = {"dtype": str(leaf.dtype), "nonzero": nonzero,
                       "fine_share": share}
        ok = ok and str(leaf.dtype) == spec["dtype"] \
            and share >= spec["fine_share_min"]
    return ok, facts


def check(ctx, model, eng, srv, items):
    """The recurrent rows' precision after ``steps`` live decode steps over
    the check's own prompts, then ``serve_backlog_routed.check``."""
    spec = ctx.traffic["check"]
    for it in serving.check_picks(items, spec["slots"]):
        srv.submit(serving.to_request(dataclasses.replace(
            it, new_tokens=spec["steps"] + 4, do_sample=False)))
    for _ in range(spec["steps"]):
        srv.step()
    with jax.set_mesh(eng.mesh):
        kept, state = state_precision(spec["state"], srv.pool)
    while srv.step():
        pass
    ctx.log(f"check (recurrent rows): {state} against {spec['state']} -> "
            f"{'ok' if kept else 'FAILED'}")
    ok, facts = _routed.check(ctx, model, eng, srv, items)
    facts.update(state_precision=state, state_kept_as_stated=kept)
    return bool(ok and kept), facts


def run(ctx):
    """``serve_backlog.run``, with this module's check."""
    _routed._base.check = check
    return _routed._base.run(ctx)
