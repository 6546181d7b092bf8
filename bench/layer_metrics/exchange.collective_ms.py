"""exchange.collective_ms: device milliseconds per step of collective ops
(all-to-all and kin), per chip."""


def read(r):
    ns = r.reduction.class_ns["collective"]
    return ns / r.steps * 1e-6 if ns > 0 else None
