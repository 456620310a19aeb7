"""Blob-shape conventions and parameter definitions.

The reference's ``Blob<Dtype>`` (``src/caffe/blob.cpp``) is a 4-D
(num, channels, height, width) tensor with a data+diff pair living in
``SyncedMemory`` and an optional parameter-server table binding. Here a blob is
just a ``jax.Array`` in NCHW layout; gradients are values produced by
``jax.grad``; and the PS-table binding becomes a ``NamedSharding`` (replicated
for DP parity with the reference, sharded for model parallelism).

``ParamDef`` captures what the reference spreads across ``Layer::SetUp`` +
``ParamSpec``/``blobs_lr``/``weight_decay``: the shape, the filler, and the
per-blob learning-rate / weight-decay multipliers.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Tuple

from ..proto.messages import FillerParameter


@dataclass(frozen=True)
class ParamDef:
    """Definition of one learnable parameter blob of a layer."""

    name: str                    # short name within the layer, e.g. "w" / "b"
    shape: Tuple[int, ...]
    filler: FillerParameter
    lr_mult: float = 1.0
    decay_mult: float = 1.0
    # the layer computes this leaf's next value itself (a top of the layer,
    # ``Layer.updates``): no gradient, optimizer, decay or clip touches it
    layer_updated: bool = False
    # fan_in for xavier-style fillers: count / shape[0], matching Caffe's
    # `blob->count() / blob->num()` (include/caffe/filler.hpp).
    @property
    def count(self) -> int:
        return int(math.prod(self.shape))

    @property
    def fan_in(self) -> int:
        return self.count // self.shape[0] if self.shape else 1


def nchw(shape: Tuple[int, ...]) -> Tuple[int, int, int, int]:
    """Pad a (possibly shorter) shape out to 4-D NCHW like Blob::Reshape."""
    if len(shape) > 4:
        raise ValueError(f"blob rank > 4: {shape}")
    return tuple(shape) + (1,) * (4 - len(shape))  # type: ignore[return-value]
