"""c2r.extend_ms: device milliseconds per step of the ops the program
names ``stage{i}.c2r_extend`` (a c2r's Hermitian extension, its flip,
conjugate and concatenate, and the real part), per chip.  Nothing where
no stage is a c2r."""


def read(r):
    s = getattr(r, "scopes", None)
    if s is None or s.kind_ops["c2r_extend"] == 0:
        return None
    return s.kind_ns["c2r_extend"] * 1e-6
