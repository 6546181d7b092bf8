"""dealias.prune_ms: device milliseconds per step of the ops the program
names ``stage{i}.prune`` (the truncated spectrum's keep and zero-scatter,
the r2c keep and zero-pad), per chip.  Nothing where no stage prunes."""


def read(r):
    s = getattr(r, "scopes", None)
    if s is None or s.kind_ops["prune"] == 0:
        return None
    return s.kind_ns["prune"] * 1e-6
