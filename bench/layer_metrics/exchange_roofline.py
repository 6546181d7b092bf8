"""exchange_roofline: the least time one step's exchanges need on the
chip's interconnect, over the device time of the collective ops
(``exchange.collective_ms``), in percent, per chip.

The bytes are the program's own count (``repro.core.spans.exchange_totals``,
read at the end of the traced run): for each executor traced in the
process, what one chip sends to the other chips in one run of it, at the
wire's dtype.  A step is taken to be one run of each traced executor,
which holds for the round-trip step kind (one forward and one backward
executor, each run once a step); that is why only its cell lists this
metric.  The least time is those bytes at the chip's published
interconnect bandwidth, held here because ``bench/peaks.py`` has none:
1,600 Gbit/s = 200 GB/s per chip (Google Cloud documentation, "TPU v5e";
the one device ``bench/peaks.py`` knows).

Nothing where the program has no such counter, it saw no exchange, no
collective ran, or the run is not on a chip."""

ICI_BYTES_PER_S = 1600e9 / 8


def read(r):
    try:
        from repro.core import spans
    except ImportError:
        return None
    totals = getattr(spans, "exchange_totals", None)
    ns = r.reduction.class_ns["collective"]
    if totals is None or r.peaks is None or ns <= 0:
        return None
    sent = sum(rec["bytes"] for rec in totals().values())
    if sent <= 0:
        return None
    return 100.0 * (sent / ICI_BYTES_PER_S) / (ns * 1e-9 / r.steps)
