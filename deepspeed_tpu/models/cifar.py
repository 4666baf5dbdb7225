"""Small CNN for CIFAR-10 — the reference's introductory training example.

Role parity: DeepSpeedExamples' `cifar10_deepspeed.py` (the tutorial
model: CIFAR-10, ZeRO-0, single process).  Convs
run through ``lax.conv_general_dilated`` in NHWC — XLA maps them onto the
MXU like matmuls.
"""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass
class CifarCNNConfig:
    num_classes: int = 10
    channels: tuple = (64, 128, 256)
    dense: int = 256
    image_size: int = 32


PRESETS = {
    "cifar-cnn": dict(),
    "cifar-cnn-tiny": dict(channels=(8, 16), dense=32, image_size=32),
}


def _conv(x, w, b):
    y = lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + b


class CifarCNN:
    """conv(3x3)+relu+maxpool stack → dense → logits (functional model)."""

    def __init__(self, config=None, preset=None, dtype=jnp.float32, **overrides):
        if config is None:
            base = dict(PRESETS[preset or "cifar-cnn"])
            base.update(overrides)
            config = CifarCNNConfig(**base)
        self.config = config
        self.dtype = dtype

    def init(self, rng):
        c = self.config
        keys = jax.random.split(rng, len(c.channels) + 2)
        params = {}
        cin = 3
        size = c.image_size
        for i, cout in enumerate(c.channels):
            fan = 3 * 3 * cin
            params[f"conv{i}"] = {
                "w": jax.random.normal(keys[i], (3, 3, cin, cout),
                                       jnp.float32) / np.sqrt(fan),
                "b": jnp.zeros((cout,), jnp.float32)}
            cin = cout
            size //= 2
        flat = size * size * cin
        params["fc1"] = {
            "w": jax.random.normal(keys[-2], (flat, c.dense),
                                   jnp.float32) / np.sqrt(flat),
            "b": jnp.zeros((c.dense,), jnp.float32)}
        params["head"] = {
            "w": jax.random.normal(keys[-1], (c.dense, c.num_classes),
                                   jnp.float32) / np.sqrt(c.dense),
            "b": jnp.zeros((c.num_classes,), jnp.float32)}
        return params

    def partition_specs(self, params=None):
        return jax.tree_util.tree_map(lambda _: P(), params) \
            if params is not None else None

    def apply(self, params, images, rng=None, deterministic=True):
        """images: (B, 32, 32, 3) float in [0, 1] → logits (B, classes)."""
        c = self.config
        x = images.astype(self.dtype)
        for i in range(len(c.channels)):
            p = params[f"conv{i}"]
            x = jax.nn.relu(_conv(x, p["w"].astype(x.dtype),
                                  p["b"].astype(x.dtype)))
            x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1),
                                  (1, 2, 2, 1), "VALID")
        x = x.reshape(x.shape[0], -1)
        x = jax.nn.relu(x @ params["fc1"]["w"].astype(x.dtype)
                        + params["fc1"]["b"].astype(x.dtype))
        logits = x.astype(jnp.float32) @ params["head"]["w"] \
            + params["head"]["b"]
        return logits

    def loss(self, params, batch, rng):
        if isinstance(batch, dict):
            images, labels = batch["images"], batch["labels"]
        else:
            images, labels = batch
        logits = self.apply(params, images, rng=rng, deterministic=False)
        logp = jax.nn.log_softmax(logits, axis=-1)
        onehot = jax.nn.one_hot(labels, self.config.num_classes)
        return -jnp.mean(jnp.sum(onehot * logp, axis=-1))

    def accuracy(self, params, images, labels):
        logits = self.apply(params, images)
        return jnp.mean(jnp.argmax(logits, -1) == labels)
