"""layout.copy_ms: device milliseconds per step of data-movement ops
(copy, transpose, pad, slice, concatenate, gather, scatter, ...; a fusion
by its root), per chip."""


def read(r):
    ns = r.reduction.class_ns["layout"]
    return ns / r.steps * 1e-6 if ns > 0 else None
