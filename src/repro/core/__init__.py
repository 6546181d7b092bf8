"""Core library: the paper's global-redistribution method and parallel FFT.

Public API:
  decompose, AxisDecomp            — balanced block decomposition (Alg. 1)
  Pencil, make_pencil              — distributed-array alignment state
  exchange, exchange_shard         — the paper's fused v→w redistribution
                                     (comm_dtype=None|"complex64"|"bf16"|
                                      "int8" wire payloads)
  exchange_shard_sliced            — the pipelined (sliced) exchange engine
  ParallelFFT                      — slab/pencil/d-dim distributed FFT
                                     (config=PlanConfig(method="fused"|
                                      "traditional"|"pipelined"|"auto"))
  quant                            — shared quantization codecs (bf16/int8)
  tuner                            — per-stage exchange-engine autotuner
"""

from repro.core.decomp import AxisDecomp, decompose, local_lengths, pad_to_multiple, start_indices
from repro.core.pencil import Pencil, group_size, make_pencil, pad_global, unpad_global
from repro.core.quant import canonical_comm_dtype
from repro.core.redistribute import (exchange, exchange_cost_bytes, exchange_shard,
                                     exchange_shard_sliced, exchange_time_model,
                                     exchange_wire_bytes)
from repro.core.pfft import ParallelFFT

__all__ = [
    "AxisDecomp",
    "decompose",
    "local_lengths",
    "pad_to_multiple",
    "start_indices",
    "Pencil",
    "group_size",
    "make_pencil",
    "pad_global",
    "unpad_global",
    "canonical_comm_dtype",
    "exchange",
    "exchange_cost_bytes",
    "exchange_shard",
    "exchange_shard_sliced",
    "exchange_time_model",
    "exchange_wire_bytes",
    "ParallelFFT",
]
