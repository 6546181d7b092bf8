"""exchange.exposed_frac: the share of the collective ops' device time
during which no other op runs on the same device, mean over chips."""


def read(r):
    ns = r.reduction.class_ns["collective"]
    return r.reduction.exposed_collective_ns / ns if ns > 0 else None
