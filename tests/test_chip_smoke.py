"""chip_smoke.py's phases at gpt2-tiny on the CPU mesh.

The chip check itself needs a TPU (``python chip_smoke.py`` through the
chip tool); what tier-1 can hold is that the SAME phase functions run end
to end — every check inside them passing — and that ``main()`` refuses a
backend that is not a TPU before it builds anything.
"""

import os
import subprocess
import sys

import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

from deepspeed_tpu.parallel.mesh import make_mesh  # noqa: E402


def test_serve_phase_tiny(devices):
    """More requests than slots — on the CPU the interpreted kernel is
    exact, so the logits must equal the gather oracle's bit for bit and
    the greedy stream sequential generate token for token."""
    eng = chip_smoke.build_server("gpt2-tiny", dtype=jnp.bfloat16, n_layer=2)
    assert eng.mesh.size == 1          # one device, not the whole host
    requests = chip_smoke.make_requests(
        eng.module.config.vocab_size, (5, 9), new_tokens=4, seed=0)
    reference = chip_smoke.reference_streams(eng, requests)
    assert sorted(reference) == [0]    # greedy, sampled alternating
    out = chip_smoke.serve_phase(eng, requests, kv_bits=16, slots=1,
                                 logit_tol=0.0, reference=reference)
    assert out["mosaic"] == 0          # interpreted: plain HLO on the CPU
    assert out["greedy_agreement"] == 1.0
    eng.close()


def test_train_phase_tiny(devices):
    """The four-chip phase: ZeRO-3 on fsdp=4, the chip trainer's remat,
    chunked loss and attention choice — falling loss and a quarter of
    the state on each device are checked inside the phase."""
    four = chip_smoke.train_phase(
        "gpt2-tiny", mesh=make_mesh({"data": 1, "fsdp": 4},
                                    devices=devices[:4]),
        zero_stage=3, micro=1, steps=3, n_layer=2, max_seq=32,
        attention_impl="auto", remat=True,
        remat_policy="names:attn_out,mlp_fc", loss_chunk=64)
    assert four["global_batch"] == 4 and len(four["losses"]) == 3
    assert sorted(four["shares"]) == [d.id for d in devices[:4]]


def test_main_refuses_the_cpu():
    """No accelerator: non-zero exit, no result line, nothing built."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "'platform': 'cpu'" in out.stdout     # says what it found first
    assert "server" not in out.stdout            # and built nothing
