"""Helpers the step kinds share: inputs made on the device from the seed,
ahead-of-time compilation, and the comparisons that decide ``correct``."""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Check:
    """One number compared with the reference, beside its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)  # NaN fails


def random_field(seed: int, shape, dtype, sharding):
    """Standard-normal data made on the device in one jitted call and placed
    with ``sharding`` (threefry values do not depend on the sharding)."""
    import jax
    import jax.numpy as jnp

    def gen(key):
        if np.dtype(dtype).kind == "c":
            kr, ki = jax.random.split(key)
            return jax.lax.complex(jax.random.normal(kr, shape, jnp.float32),
                                   jax.random.normal(ki, shape, jnp.float32))
        return jax.random.normal(key, shape, jnp.float32)

    return jax.jit(gen, out_shardings=sharding)(jax.random.key(seed))


def sample_index(seed: int, below: int) -> int:
    """The step whose answer is compared, drawn from the seed."""
    return int(np.random.default_rng(seed).integers(below))


def gaps(got: np.ndarray, ref: np.ndarray) -> tuple[float, float]:
    """``(rel_l2, worst)`` of ``got`` against ``ref``, in float64:
    ``||got - ref|| / ||ref||``, and ``max |got - ref| / max |ref|``, where
    one wrong element shows even when the L2 share of a large array hides
    it."""
    d = np.abs(got.astype(ref.dtype) - ref)
    a = np.abs(ref)
    return (float(np.linalg.norm(d.ravel()) / np.linalg.norm(a.ravel())),
            float(np.max(d) / np.max(a)))
