"""Plan configuration — the single validated surface for ``ParallelFFT``.

Two types live here:

:class:`PlanConfig` — a frozen dataclass holding every execution knob of
    a plan (``method`` / ``impl`` / ``exchange_impl`` / ``chunks`` /
    ``comm_dtype`` / ``batch_fusion`` / ``tuner_cache`` / ``guard``).
    All validation happens in one place (``__post_init__``), so every
    consumer — the plan itself, the tuner, the benchmarks — sees an
    already-canonical config: ``ParallelFFT(mesh, shape, grid,
    config=PlanConfig(...))``, read back as ``plan.config``.

:class:`StageEntry` — one exchange stage's tuned/selected execution
    entry: ``(method, chunks, comm_dtype, impl, batch_fusion)``.  Being a
    NamedTuple it unpacks and indexes like the 5-field row the tuner's
    cache stores (``entry[2]`` is the comm_dtype), and
    :meth:`StageEntry.make` validates any such row.

The ``impl`` stage field selects the *exchange-local* implementation:

``"jnp"``    — the reference path: :mod:`repro.core.quant` codecs plus the
    engine's jnp pack/unpack copies (multiple HBM round-trips).
``"pallas"`` — the fused exchange kernels of
    :mod:`repro.kernels.exchange`: quantize/narrow + chunk-layout
    pack fused into one HBM-read → VMEM → HBM-write pass on the encode
    side, and dequantize + unpack-transpose fused on the decode side, so
    the only HBM traffic between 1-D FFTs is the collective itself (the
    paper's no-realignment thesis, now holding for lossy wire payloads
    too).  Interpret mode makes the same kernels run on CPU.

Note this is distinct from the plan-level ``impl`` (the local *FFT*
implementation, ``"jnp"`` | ``"matmul"``); the exchange impl is
``PlanConfig.exchange_impl`` and per-stage ``StageEntry.impl``.
"""

from __future__ import annotations

from dataclasses import dataclass, fields, replace
from typing import NamedTuple

from repro.core.quant import canonical_comm_dtype

#: exchange-local implementations a stage entry may carry
EXCHANGE_IMPLS = ("jnp", "pallas")

#: batch_fusion execution modes for a stacked multi-field exchange stage
#: (mirrored by repro.core.redistribute.BATCH_FUSIONS, which re-exports it)
BATCH_FUSIONS = ("stacked", "pipelined-across-fields", "per-field")

#: exchange engines a stage entry may carry ("auto" is plan-level only)
METHODS = ("fused", "traditional", "pipelined")


class StageEntry(NamedTuple):
    """One exchange stage's execution entry.

    Unpacks/indexes like the cache's rows: ``entry[0]`` method,
    ``entry[1]`` chunks, ``entry[2]`` comm_dtype, ``entry[3]`` impl and
    ``entry[4]`` batch_fusion.
    """

    method: str
    chunks: int
    comm_dtype: str
    impl: str = "jnp"
    batch_fusion: str = "stacked"

    @classmethod
    def make(cls, entry) -> "StageEntry":
        """Normalize a StageEntry or a 5-field ``(method, chunks,
        comm_dtype, impl, batch_fusion)`` row into a validated
        StageEntry."""
        if isinstance(entry, cls):
            return entry.validate()
        t = tuple(entry)
        if len(t) != 5:
            raise ValueError(f"schedule entry {entry!r} has {len(t)} fields; expected 5")
        return cls(t[0], int(t[1]), t[2], t[3], t[4]).validate()

    def validate(self) -> "StageEntry":
        if self.method not in METHODS:
            raise ValueError(f"unknown method {self.method!r}; expected one of {METHODS}")
        if self.chunks < 1:
            raise ValueError(f"chunks must be >= 1, got {self.chunks}")
        if self.impl not in EXCHANGE_IMPLS:
            raise ValueError(f"unknown exchange impl {self.impl!r}; expected one of {EXCHANGE_IMPLS}")
        if self.batch_fusion not in BATCH_FUSIONS:
            raise ValueError(
                f"unknown batch_fusion {self.batch_fusion!r}; expected one of {BATCH_FUSIONS}")
        d = canonical_comm_dtype(self.comm_dtype)
        return self if d == self.comm_dtype else self._replace(comm_dtype=d)


def as_schedule(entries) -> tuple[StageEntry, ...]:
    """Normalize an iterable of schedule entries into a tuple of
    :class:`StageEntry` — the one normalizer every consumer of a
    user/disk-provided schedule shares."""
    return tuple(StageEntry.make(e) for e in entries)


@dataclass(frozen=True)
class PlanConfig:
    """Validated execution config for one :class:`~repro.core.pfft.ParallelFFT`.

    Fields (see the ParallelFFT docstring for full semantics):

    method:        "fused" (paper) | "traditional" | "pipelined" | "auto".
    impl:          local 1-D FFT implementation ("jnp" | "matmul").
    exchange_impl: exchange-local pack/codec implementation ("jnp" |
                   "pallas").  Explicit methods run every stage with it;
                   for ``method="auto"`` it is a *candidate budget* — the
                   tuner sweeps pallas kernels (where applicable) only
                   when this is "pallas", and picks them per stage only
                   where they win.
    chunks:        slice count for method="pipelined".
    comm_dtype:    wire payload policy / accuracy budget (canonicalized).
    batch_fusion:  multi-field execution mode for the explicit methods.
    tuner_cache:   schedule-cache path for method="auto".
    guard:         runtime-guard mode ("off" | "strict" | "degrade").
    """

    method: str = "fused"
    impl: str = "jnp"
    exchange_impl: str = "jnp"
    chunks: int = 4
    comm_dtype: str | None = None
    batch_fusion: str = "stacked"
    tuner_cache: str | None = None
    guard: str = "off"

    def __post_init__(self):
        if self.method not in (*METHODS, "auto"):
            raise ValueError(f"unknown method {self.method!r}; expected one of {(*METHODS, 'auto')}")
        if self.impl not in ("jnp", "matmul"):
            raise ValueError(f"unknown FFT impl {self.impl!r}; expected 'jnp' or 'matmul'")
        if self.exchange_impl not in EXCHANGE_IMPLS:
            raise ValueError(
                f"unknown exchange_impl {self.exchange_impl!r}; expected one of {EXCHANGE_IMPLS}")
        if self.chunks < 1:
            raise ValueError(f"chunks must be >= 1, got {self.chunks}")
        if self.batch_fusion not in BATCH_FUSIONS:
            raise ValueError(
                f"unknown batch_fusion {self.batch_fusion!r}; expected one of {BATCH_FUSIONS}")
        # lazy import-cycle-free guard-mode check (health has no core deps)
        from repro.robustness.health import GUARD_MODES

        if self.guard not in GUARD_MODES:
            raise ValueError(f"unknown guard {self.guard!r}; expected one of {GUARD_MODES}")
        object.__setattr__(self, "comm_dtype", canonical_comm_dtype(self.comm_dtype))

    def replace(self, **changes) -> "PlanConfig":
        """Functional update (re-validates through ``__post_init__``)."""
        return replace(self, **changes)

    def stage_entry(self) -> StageEntry:
        """The uniform StageEntry an explicit-method config implies for
        every exchange stage (``method="auto"`` resolves per stage via the
        tuner instead)."""
        chunks = self.chunks if self.method == "pipelined" else 1
        return StageEntry(self.method, chunks, self.comm_dtype,
                          self.exchange_impl, self.batch_fusion)

    def to_dict(self) -> dict:
        return {f.name: getattr(self, f.name) for f in fields(self)}
