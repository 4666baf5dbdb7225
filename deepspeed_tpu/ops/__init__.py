"""Op registry.

TPU equivalent of the reference's ``op_builder/`` JIT-compile matrix
(``op_builder/builder.py:107 OpBuilder``): instead of compiling CUDA at import
time, ops register an implementation per backend with an ``is_compatible``
probe; ``report()`` mirrors ``ds_report`` (``deepspeed/env_report.py:24``).
"""

import functools

import jax


@functools.lru_cache(maxsize=None)
def backend():
    return jax.default_backend()


def flash_attention_available():
    """Pallas flash attention runs on TPU; elsewhere the jnp path is used.
    Decided by the platform alone: on a TPU a kernel that cannot be
    imported or compiled raises where it is used, it is not replaced."""
    return backend() == "tpu"


OP_REGISTRY = {}


def register_op(name, compatible_backends=("tpu", "cpu")):
    def deco(fn):
        OP_REGISTRY[name] = {"fn": fn, "backends": tuple(compatible_backends)}
        return fn
    return deco


def is_compatible(name):
    entry = OP_REGISTRY.get(name)
    return entry is not None and backend() in entry["backends"]


def report():
    """ds_report equivalent: op → (registered, compatible-with-this-backend)."""
    lines = [f"backend: {backend()}"]
    for name, entry in sorted(OP_REGISTRY.items()):
        lines.append(f"op {name}: registered=True "
                     f"compatible={backend() in entry['backends']}")
    lines.append(f"flash_attention: available={flash_attention_available()}")
    return "\n".join(lines)
