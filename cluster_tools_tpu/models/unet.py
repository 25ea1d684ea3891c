"""3D U-Net in flax, bfloat16-friendly, with anisotropic pooling.

Design notes (TPU-first):
  * convs are 3x3x3 (or 1x3x3 on anisotropic levels) NCDHW→NDHWC transposed
    internally — XLA tiles channels-last convs onto the MXU;
  * default compute dtype bfloat16 with float32 params — the MXU-native mix;
  * group norm (batch-size independent, works at batch 1 per block);
  * the whole forward is shape-static per block geometry, so one compiled
    program serves every block.

The architecture mirrors what the reference's external pytorch checkpoints
implement (neurofire-style UNet3D; reference inference/frameworks.py wraps
them but the repo defines none itself).
"""

from __future__ import annotations

import json
import os
import pickle
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn
import numpy as np
from flax import serialization


def _scale3(sf) -> Tuple[int, int, int]:
    return (sf,) * 3 if isinstance(sf, int) else tuple(sf)


class ConvBlock(nn.Module):
    features: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        for _ in range(2):
            x = nn.Conv(self.features, (3, 3, 3), padding="SAME",
                        dtype=self.dtype)(x)
            x = nn.GroupNorm(
                num_groups=min(8, self.features), dtype=jnp.float32
            )(x.astype(jnp.float32))
            x = nn.relu(x).astype(self.dtype)
        return x


class UNet3D(nn.Module):
    """Encoder/decoder with skip connections.

    in/out layout: [batch, channel, z, y, x] (the block convention used by the
    tasks); internally channels-last for MXU-friendly convs.
    """

    out_channels: int = 3
    initial_features: int = 16
    depth: int = 3
    scale_factors: Optional[Sequence] = None  # per-level, e.g. [[1,2,2],2]
    final_activation: Optional[str] = "sigmoid"
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        # NCDHW → NDHWC
        x = jnp.transpose(x, (0, 2, 3, 4, 1)).astype(self.dtype)
        scales = list(self.scale_factors or [2] * (self.depth - 1))
        if len(scales) != self.depth - 1:
            raise ValueError("need depth-1 scale factors")
        feats = [self.initial_features * (2**i) for i in range(self.depth)]

        skips = []
        for level in range(self.depth - 1):
            x = ConvBlock(feats[level], self.dtype)(x)
            skips.append(x)
            sf = _scale3(scales[level])
            x = nn.max_pool(x, window_shape=sf, strides=sf)
        x = ConvBlock(feats[-1], self.dtype)(x)
        for level in reversed(range(self.depth - 1)):
            sf = _scale3(scales[level])
            target = skips[level]
            x = jax.image.resize(
                x,
                x.shape[:1] + target.shape[1:4] + x.shape[-1:],
                method="nearest",
            )
            x = nn.Conv(feats[level], (1, 1, 1), dtype=self.dtype)(x)
            x = jnp.concatenate([target, x], axis=-1)
            x = ConvBlock(feats[level], self.dtype)(x)
        x = nn.Conv(self.out_channels, (1, 1, 1), dtype=jnp.float32)(
            x.astype(jnp.float32)
        )
        if self.final_activation == "sigmoid":
            x = jax.nn.sigmoid(x)
        elif self.final_activation == "softmax":
            x = jax.nn.softmax(x, axis=-1)
        # NDHWC → NCDHW
        return jnp.transpose(x, (0, 4, 1, 2, 3))


MODEL_REGISTRY = {"UNet3D": UNet3D}


def save_checkpoint(path: str, params, model_config: Dict[str, Any]) -> None:
    """Checkpoint = flax msgpack params + JSON model config sidecar."""
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "params.msgpack"), "wb") as f:
        f.write(serialization.to_bytes(params))
    with open(os.path.join(path, "model.json"), "w") as f:
        json.dump(model_config, f, indent=2)


def load_checkpoint(path: str):
    """Returns (model, params). ``model.json`` carries the constructor args
    plus ``"model": "UNet3D"``."""
    with open(os.path.join(path, "model.json")) as f:
        conf = json.load(f)
    name = conf.pop("model", "UNet3D")
    in_channels = conf.pop("in_channels", 1)
    if "dtype" in conf:
        # mixed-precision knob: "bfloat16" (default — MXU-native compute
        # with float32 params/norms) or "float32" for full precision
        conf["dtype"] = jnp.dtype(conf["dtype"])
    model = MODEL_REGISTRY[name](**conf)
    # template params to restore structure
    dummy = jnp.zeros((1, in_channels, 8, 16, 16), jnp.float32)
    template = model.init(jax.random.PRNGKey(0), dummy)
    with open(os.path.join(path, "params.msgpack"), "rb") as f:
        params = serialization.from_bytes(template, f.read())
    return model, params
