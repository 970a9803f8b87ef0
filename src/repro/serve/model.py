"""The served-model handle: what the serving layer needs to know of a
network, so that one executor cache, one engine and one tuner serve
more than the CIFAR BNN.

A :class:`ServedModel` is its serve fn (``(engine=, conv_impl=,
blocks=, ragged=, mesh=) -> jit'd (packed, images) -> logits``), the
shape and dtype of one request image, and the bit-identical failover
ladder over its engines. :data:`CIFAR_BNN` is the default everywhere,
so every call that names no model serves the CIFAR network exactly as
before; ``core.bireal.served_model()`` is Bi-Real Net-18's handle.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping

import numpy as np

from repro.core.bnn import SERVE_FALLBACKS, bnn_serve_fn


@dataclasses.dataclass(frozen=True)
class ServedModel:
    """A served network: ``serve_fn(engine=, conv_impl=, blocks=,
    ragged=, mesh=)`` builds one executor's jit'd forward; requests are
    ``[n, *image_shape]`` of ``dtype``; ``fallbacks`` is the failover
    ladder over its engines; ``name`` keys its executors."""

    name: str
    serve_fn: Callable[..., Callable]
    image_shape: tuple[int, ...]
    dtype: Any
    fallbacks: Mapping[str, tuple]

    def check_images(self, images: np.ndarray) -> np.ndarray:
        """``images`` as a request of this model: ``[n, *image_shape]``,
        and of its dtype where that is not a float (a float request
        reaching a uint8 model would wrap, not round)."""
        images = np.asarray(images)
        if images.shape[1:] != self.image_shape:
            raise ValueError(
                f"request rows must be {self.image_shape} images, got "
                f"{images.shape[1:]}"
            )
        if (np.dtype(self.dtype).kind != "f"
                and images.dtype != np.dtype(self.dtype)):
            raise ValueError(
                f"{self.name} takes {np.dtype(self.dtype).name} images, "
                f"got {images.dtype.name}"
            )
        return images


CIFAR_BNN = ServedModel(name="bnn_cifar10", serve_fn=bnn_serve_fn,
                        image_shape=(32, 32, 3), dtype=np.float32,
                        fallbacks=SERVE_FALLBACKS)

__all__ = ["CIFAR_BNN", "ServedModel"]
