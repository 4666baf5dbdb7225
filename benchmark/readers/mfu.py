"""Model FLOP/s utilisation of a training cell, in percent: the FLOPs the
forward and backward passes need per token (the family's
``matmul_params_per_token`` and causal attention from its ``dims``, no
recomputation counted: ``costs.train_flops_per_token_of``) times the tokens
of the whole steps inside the profiler's capture over the time those steps
took, over chips times the chip's published bf16 peak.  The steps are the
runner's own ``span`` rows that lie inside the capture, timed from the end
of the first to the end of the last: a span ends where a loss is read, so
that is the time between steps' ends, and the first span (which may begin
with the device drained by the profiler's start) only sets the clock.

The step rate, not the traced run's whole window: a traced run stalls where
the profiler starts, and once read 29.3 beside a step time that says 36.4
(ledger, PR 24, ``train_z1``; PERF.md, PR 27)."""

from benchmark import costs


def read(view, span):
    f, peaks = view["facts"], view["peaks"]
    t0, t1 = view["trace_span"]
    if peaks is None or t0 is None or "tokens_per_step" not in f:
        return None
    ends = [t + d for name, t, d in view["spans"].rows
            if name == span and t0 <= t and t + d <= t1]
    if len(ends) < 2:
        return None
    need = costs.train_flops_per_token_of(
        f["matmul_params_per_token"], f["n_head"] * f["head_dim"],
        f["n_layer"], f["seq"])
    tokens_per_s = (len(ends) - 1) * f["tokens_per_step"] / (
        ends[-1] - ends[0])
    return 100.0 * need * tokens_per_s / (
        f["chips"] * peaks["bf16_flops_per_s"])
