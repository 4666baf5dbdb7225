"""What needs a stream to be nothing but its K/V blocks, and who is refused it.

``prefix_cache``, ``kv_snapshot``, ``transfer`` and a ``role`` move or share a
stream BY the ``k`` / ``v`` blocks of one growing table.  Four kinds of
serving state are more than that (recurrent rows, a ring of window blocks, a
latent row, a table that folds its windows into summary blocks), and ``inference/serving.py`` keeps ONE table of them
(``_NEEDS_BLOCKS_ALONE``): every family that shows a kind is refused every
such feature at construction, by the feature's name, with the kind's phrase
and an anchor of ``docs/serving.md`` that exists."""

import os
import re

import pytest
import jax.numpy as jnp

import deepspeed_tpu as ds
from deepspeed_tpu.models import build
from deepspeed_tpu.inference import ServingEngine

DOCS = os.path.join(os.path.dirname(__file__), os.pardir, "docs", "serving.md")

# family -> (preset, what the error calls its kind of state, the kind's anchor)
FAMILIES = {
    "jamba": ("jamba-tiny", "recurrent state", "recurrent-state"),
    "nemotron_h": ("nemotron-h-tiny", "recurrent state", "recurrent-state"),
    "qwen3_next": ("qwen3-next-tiny", "recurrent state", "recurrent-state"),
    "trinity": ("afmoe-tiny", "sliding-window layers", "window-layers"),
    "deepseek_v2": ("deepseek-v2-tiny", "a latent KV pool", "latent-pool"),
    "evabyte": ("evabyte-tiny", "a cache that folds its windows",
                "compacted-window")}
FEATURES = {"prefix_cache": ("prefix_cache", True),
            "kv_snapshot": ("kv_snapshot", {"every_tokens": 4}),
            "transfer": ("transfer", {"dir": "/nonexistent"}),
            "role=prefill": ("role", "prefill"),
            "role=decode": ("role", "decode")}


@pytest.fixture(scope="module")
def engines():
    """One inference engine a family, built when a test first asks."""
    built = {}

    def engine(family):
        if family not in built:
            m = build(FAMILIES[family][0], dtype=jnp.float32,
                      max_position_embeddings=64)
            built[family] = ds.init_inference(m, dtype=jnp.float32)
        return built[family]
    return engine


@pytest.fixture(scope="module")
def headings():
    """The anchors ``docs/serving.md``'s headings give."""
    with open(DOCS) as f:
        return {re.sub(r"[^a-z0-9 -]", "", line.lstrip("#").strip().lower())
                .replace(" ", "-") for line in f if line.startswith("#")}


@pytest.mark.parametrize("feature", list(FEATURES))
@pytest.mark.parametrize("family", list(FAMILIES))
def test_what_needs_a_stream_to_be_its_blocks_is_refused_by_name(
        engines, headings, family, feature):
    _, what, anchor = FAMILIES[family]
    name, value = FEATURES[feature]
    said = (rf"^serving\.{name}=.* cannot serve a model with {what}: .+ "
            rf"\(docs/serving\.md#{anchor}\)$")
    with pytest.raises(ValueError, match=said):
        ServingEngine(engine=engines(family), config={
            "batch_slots": 2, "journal_dir": "/nonexistent",
            # the tiny fold's 4 summary rows a window are whole blocks
            "block_size": 4 if family == "evabyte" else 8,
            name: value})
    assert anchor in headings

