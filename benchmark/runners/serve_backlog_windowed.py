"""Traffic kind ``serve_backlog_windowed``: ``serve_backlog_routed`` (its
backlog, its window, its rows, its ``serve_tokens_per_s`` and its
route-before-logits check, number for number: this module runs ITS ``run`` and
ITS ``check``) for a model whose serving state holds TWO kinds of block
(``docs/serving.md#window-layers``): the growing table's and a window layer's
ring, each under an allocator of its own.

``serve_backlog_routed``'s check asks whether every block came home of ONE
allocator (``srv.allocator``).  This one asks the same of the second
(``srv.window_allocator``), and that the comparison really crossed the
window: at least ``rows_past_window_min`` of the compared rows are longer
than the model's ``sliding_window``, so their rings have wrapped and a key
that should have slid out would show in the logits.
"""

from benchmark import harness

_routed = harness.load_plugin("runners", "serve_backlog_routed")
backlog = _routed.backlog
compare = _routed.compare
route_ids = _routed.route_ids


def check(ctx, model, eng, srv, items):
    """``serve_backlog_routed.check``, then the second kind of block and the
    rows past the window."""
    ok, facts = _routed.check(ctx, model, eng, srv, items)
    spec = ctx.traffic["check"]
    window = ctx.config["sliding_window"]
    past = sum(n > window for n in facts["reference_rows"])
    recycled = (srv.window_allocator.free_blocks
                == srv.window_num_blocks - 1)
    facts.update(window_blocks_recycled=recycled, rows_past_window=past,
                 rows_past_window_min=spec["rows_past_window_min"])
    ok = bool(ok and recycled and past >= spec["rows_past_window_min"])
    ctx.log(f"check (two kinds of block): window blocks recycled {recycled}, "
            f"{past} compared rows past the window of {window} -> "
            f"{'ok' if ok else 'FAILED'}")
    return ok, facts


def run(ctx):
    """``serve_backlog.run``, with this module's check."""
    _routed._base.check = check
    return _routed._base.run(ctx)
