"""Nominal work of the local 1-D FFT stages, from shapes alone.

The count depends only on the global shape, the per-axis transforms of
the configuration and the dtypes; never on how the program implements a
stage (XLA's FFT, the four-step kernel, a Hermitian-extension c2r).  A
change of implementation therefore cannot move it.

Conventions:

- A plan applies its 1-D transforms in descending axis order (the last
  axis first), forward; backward is the exact reverse, with the same
  count.
- Flops: ``5 n log2 n`` per 1-D transform of ``n`` points, times the
  number of such transforms (the product of the other axes' current
  extents); half of that where the stage's data is real (r2c forward,
  c2r backward).
- Bytes: each stage reads its block once and writes its block once, at
  the block's current logical extents and dtype width: float32 (4 bytes)
  before the r2c stage, complex64 (8 bytes) after.  A pruned axis counts
  at its retained extent once it has been transformed.

An axis transform is a dict ``{"kind": "c2c" | "r2c", "keep": int | None}``
as the configuration files write it.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

REAL_BYTES = 4
COMPLEX_BYTES = 8


@dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def __mul__(self, k: float) -> "Work":
        return Work(self.flops * k, self.bytes * k)

    __rmul__ = __mul__

    def least_seconds(self, flops_per_s: float, bytes_per_s: float) -> tuple[float, str]:
        """The larger of the two roofline bounds, and which one binds."""
        t_flops = self.flops / flops_per_s
        t_bytes = self.bytes / bytes_per_s
        return (t_bytes, "hbm") if t_bytes >= t_flops else (t_flops, "flops")


def spectral_extent(n: int, axis_transform: dict) -> int:
    kind, keep = axis_transform["kind"], axis_transform.get("keep")
    if kind not in ("c2c", "r2c"):
        raise ValueError(f"no nominal count for transform kind {kind!r}")
    base = n // 2 + 1 if kind == "r2c" else n
    if keep is not None:
        if not 1 <= keep <= base:
            raise ValueError(f"keep={keep} outside 1..{base} for n={n}")
        return keep
    return base


def stage_works(shape, transforms) -> list[Work]:
    """Work of each forward stage of one field, in the order applied."""
    if len(shape) != len(transforms):
        raise ValueError("one transform per axis")
    real = transforms[-1]["kind"] == "r2c"
    if any(t["kind"] == "r2c" for t in transforms[:-1]):
        raise ValueError("only the last axis may be r2c")
    ext = list(shape)
    itemsize = REAL_BYTES if real else COMPLEX_BYTES
    works = []
    for axis in reversed(range(len(shape))):
        n = shape[axis]
        in_elems = math.prod(ext)
        ntrans = in_elems // ext[axis]
        flops = 5.0 * n * math.log2(n) * ntrans * (0.5 if real else 1.0)
        ext[axis] = spectral_extent(n, transforms[axis])
        nbytes = in_elems * itemsize + math.prod(ext) * COMPLEX_BYTES
        works.append(Work(flops, float(nbytes)))
        itemsize, real = COMPLEX_BYTES, False
    return works


def field_transform(shape, transforms) -> Work:
    """Work of one field's forward (or backward) transform."""
    return sum(stage_works(shape, transforms), Work(0.0, 0.0))
