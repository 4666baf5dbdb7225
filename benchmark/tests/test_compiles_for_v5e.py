"""The kernels of the benchmark's two configurations, compiled by the real
Mosaic + XLA:TPU compiler for a DESCRIBED v5e (no chip attached): what the
chip's compiler would refuse is refused here, at no chip time.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU's library, and every pytest worker imports
every test file.  All such tests live in this one file.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

# (n_head, head_dim, seq, micro batch) as the cells run them
GPT2_LARGE = (20, 64, 1024, 4)
CEREBRAS_1P3B = (16, 128, 2048, 1)
SLOTS, BLOCK, MAX_SEQ, LAYERS = 16, 16, 2048, 2


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds its lock
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def compile_for(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).compile()


def paged_call(one_chip, n_head, head_dim, kv_bits):
    from deepspeed_tpu.ops.transformer.paged_attention import paged_attention
    width = n_head * head_dim
    nb_max = MAX_SEQ // BLOCK
    blocks = SLOTS * nb_max + 1
    payload = jnp.bfloat16 if kv_bits == 16 else jnp.int8
    pool = {"k": ((LAYERS, blocks, BLOCK, width), payload),
            "v": ((LAYERS, blocks, BLOCK, width), payload)}
    if kv_bits == 8:
        scales = (LAYERS, blocks, BLOCK, width // 64)
        pool["k_scale"] = (scales, jnp.float32)
        pool["v_scale"] = (scales, jnp.float32)
    names = sorted(pool)

    def fn(q, tables, lengths, *leaves):
        return paged_attention(q, dict(zip(names, leaves)), tables, lengths,
                               1, mode="online", interpret=False)
    return compile_for(
        one_chip, fn, ((SLOTS, 1, n_head, head_dim), jnp.bfloat16),
        ((SLOTS, nb_max), jnp.int32), ((SLOTS,), jnp.int32),
        *[pool[n] for n in names])


@pytest.mark.parametrize("kv_bits", [16, 8])
@pytest.mark.parametrize("n_head,head_dim", [GPT2_LARGE[:2],
                                             CEREBRAS_1P3B[:2]])
def test_paged_kernel_compiles(one_chip, n_head, head_dim, kv_bits):
    text = paged_call(one_chip, n_head, head_dim, kv_bits).as_text()
    assert text.count("tpu_custom_call") >= 1


def test_paged_kernel_refuses_gpt2_xl(one_chip):
    """GPT-2 XL (25 heads of 64: n_embd 1600) cannot be served: the pool's
    last dimension is H x hd and Mosaic wants it aligned to 128 lanes.  The
    finding that cost PR 23 its time, kept as a test."""
    with pytest.raises(Exception, match="aligned to tiling"):
        paged_call(one_chip, 25, 64, 16)


@pytest.mark.parametrize("n_head,head_dim,seq,batch",
                         [GPT2_LARGE, CEREBRAS_1P3B])
def test_flash_forward_backward_compiles(one_chip, monkeypatch, n_head,
                                         head_dim, seq, batch):
    import importlib
    fa = importlib.import_module(
        "deepspeed_tpu.ops.transformer.flash_attention")
    flash_attention = fa.flash_attention
    # the kernel asks jax.default_backend(), which is the CPU here: steer it
    # to the compiled path in the test, not through an option of the program
    monkeypatch.setattr(fa, "_interpret", lambda: False)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()
    shape = ((batch, seq, n_head, head_dim), jnp.bfloat16)
    text = compile_for(one_chip, jax.grad(loss, argnums=(0, 1, 2)),
                       shape, shape, shape).as_text()
    # the forward and the backward are separate Mosaic kernels
    assert text.count("tpu_custom_call") >= 2
