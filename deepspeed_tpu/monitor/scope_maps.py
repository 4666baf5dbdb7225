"""The program's map from a device trace's events to its own scopes.

A profiler capture names a device event by its HLO instruction
(``fusion.180``) inside its XLA module (``jit_step``); the
``jax.named_scope`` the instruction works for is in the executable's HLO
text alone.  ``runtime/compile_cache.CachedStep`` reads that text once,
where an executable is BUILT (``analysis/hlo_scopes.scope_map``), keeps the
map with the executable's AOT entry, and notes it here when the executable
is built or loaded.  :func:`device_scopes` hands every map noted so far to
whoever reads a capture (``benchmark/device_scopes.py``), after the engines
are closed too.  Nothing here runs inside a step.
"""

AMBIGUOUS = "ambiguous"
# in the AOT store's key: bump when ``analysis/hlo_scopes.scope_map`` books
# an instruction otherwise, or a scope is renamed with no change to a program
VERSION = 2

SERVED = ("gpt2", "jamba", "ouro", "deepseek_v2", "afmoe", "nemotron_h",
          "phi4flash", "longcat_flash", "qwen3_next", "evabyte")
TRAIN = "train"
_MIXERS = ("jamba", "nemotron_h", "phi4flash")
_RECURRENT = _MIXERS + ("qwen3_next",)
_ROUTED = ("deepseek_v2", "afmoe", "nemotron_h", "longcat_flash",
           "qwen3_next")
_DELTA = ("qwen3_next",)
_FOLDED = ("evabyte",)
_LATENT = ("deepseek_v2", "longcat_flash")
# The one vocabulary of device scopes: ``{scope: (who opens it, what it
# covers)}``, "who" the served families (``models/<family>.py``'s decode
# step or prefill) and ``train`` (the GPT-2 train step).  Same work, same
# name in every family.  ``docs/monitoring.md#device-scopes`` is this table
# and ``tests/test_device_scopes.py`` holds the two, and every family's
# executables, to each other.
VOCABULARY = {
    "embed": (SERVED + (TRAIN,), "the token (and position) embedding rows"),
    "attention": (SERVED + (TRAIN,),
                  "an attention sub-layer outside its core: norm, q/k/v and "
                  "output projections, residual (GPT-2: the whole sub-layer)"),
    "mlp": (("gpt2", "jamba", "ouro", "deepseek_v2", "afmoe", "phi4flash",
             "longcat_flash", "evabyte", TRAIN),
            "a dense feed-forward sub-layer with its norm and residual"),
    "blocks": ((TRAIN,), "the layer loop of the GPT-2 training forward"),
    "lm_head": (SERVED + (TRAIN,), "the final norm and the vocabulary "
                "matmul (a train step's chunked loss with it)"),
    "sentinel": (SERVED + (TRAIN,),
                 "the non-finite checks: rows_nonfinite on the served "
                 "logits, tree_nonfinite on a train step's gradients"),
    "sample": (SERVED, "the serving step's and prefill's token sampling"),
    "optimizer": ((TRAIN,), "gradient clipping, the optimizer's update, the "
                  "skip-step's select and the cast of the new parameters"),
    "kv.seat": (SERVED, "the K/V (or latent row) pool writes"),
    "rope": (("ouro", "afmoe") + _LATENT + _FOLDED, "the rotary table "
             "lookup and rotation of queries and keys"),
    "attn.window": (("afmoe", "phi4flash"),
                    "a sliding-window layer's attention core, prefill band "
                    "and decode ring alike"),
    "attn.global": (("afmoe",), "a full-attention layer's core"),
    "attn.gate": (("afmoe", "qwen3_next"), "the attention output gate"),
    "attn.shared": (("phi4flash",),
                    "attention over the one shared K/V: the full layer and "
                    "every cross layer"),
    "cross.last": (("phi4flash",),
                   "the cross-decoder of a prefill, run at the last "
                   "position alone (what no inner scope covers)"),
    "gmu": (("phi4flash",), "a gated memory unit"),
    "mla.absorb": (_LATENT, "W_UK into the queries, W_UV out of the result"),
    "mla.attend": (_LATENT, "latent attention's core, expanded (prefill) or "
                   "absorbed (decode kernel)"),
    "ssm.proj": (_MIXERS, "a Mamba mixer outside its recurrence: norm, "
                 "in/x/dt/out projections, gate"),
    "ssm.conv": (_MIXERS, "the causal convolution and its activation"),
    "ssm.scan": (_MIXERS, "a prompt's recurrence (the scan kernel)"),
    "ssm.step": (_RECURRENT, "one token's state update and the write of "
                 "every slot's recurrent rows"),
    "ssm.seat": (_RECURRENT, "a prefill's state written into its slot"),
    "gdn.project": (_DELTA, "a Gated DeltaNet mixer up to its recurrence: "
                    "norm, the two input products, the convolution, the "
                    "l2 norms, beta and the decay"),
    "gdn.chunk": (_DELTA, "a prompt's chunked delta rule"),
    "gdn.gate_norm": (_DELTA, "the gated norm a head and the output "
                      "projection"),
    "eva.summarise": (_FOLDED, "the fold: a window's exact K/V rows into "
                      "one summary row a chunk, in a prompt's whole windows "
                      "and for a window that ends in decoding"),
    "eva.prompt_attention": (_FOLDED, "a prompt window's (or tail's) "
                             "queries over the summaries before it and its "
                             "own keys, one softmax"),
    "eva.decode_attention": (_FOLDED, "a decode step's attention over a "
                             "slot's table of summary rows and window rows "
                             "(round the paged kernel)"),
    "moe.route": (_ROUTED, "router scores, top-k, the step's counters"),
    "moe.experts": (_ROUTED, "the held experts' grouped products and the "
                    "sort of the pairs by expert"),
    "moe.gather": (_ROUTED, "inside moe.experts: the pairs' rows gathered "
                   "in, the result rows brought back to their tokens and "
                   "the weighted sum"),
    "moe.shared": (("deepseek_v2", "afmoe", "nemotron_h", "qwen3_next"),
                   "the shared experts"),
    "moe.zero": (("longcat_flash",), "the identity experts' part"),
    "ut.loop": (("ouro",), "one pass of the looped layers outside their "
                "sub-layers: the stacked weights' slices"),
    "prefill_flash_attention": (
        ("afmoe", "nemotron_h", "qwen3_next"),
        "names the prompt's flash kernel in a capture (TPU only)"),
}
TPU_ONLY = ("prefill_flash_attention",)
# scopes that only wrap a loop over other scopes' work: in a fusion they
# give way to the scope whose work the loop's plumbing was fused into
CONTAINERS = ("blocks", "ut.loop", "cross.last")

_MAPS = {}          # module name -> {instruction: scope}
_PENDING = []       # executables of a store without maps: read when asked


def note(module, instructions):
    """One executable's map.  Executables that share a module name (two
    engines in one process) share a map: an instruction they book
    differently reads :data:`AMBIGUOUS`."""
    have = _MAPS.setdefault(module, {})
    for name, scope in instructions.items():
        scope = tuple(scope) if isinstance(scope, list) else scope
        if have.setdefault(name, scope) != scope:
            have[name] = AMBIGUOUS


def note_later(describe):
    """An executable loaded from an entry that holds no map (a store an
    older commit wrote): ``describe() -> (module, instructions)`` is called
    when the maps are first asked for, not at load."""
    _PENDING.append(describe)


def device_scopes():
    """``{module name: {instruction name: scope}}`` of every executable the
    process has acquired through ``CachedStep``: a scope's name, a sorted
    tuple of names for a fusion over several, ``""`` for an instruction no
    scope covers, :data:`AMBIGUOUS` where same-named modules disagree."""
    while _PENDING:
        got = _PENDING.pop()()
        if got is not None:
            note(*got)
    return {module: dict(have) for module, have in _MAPS.items()}


def reset():
    _MAPS.clear()
    del _PENDING[:]
