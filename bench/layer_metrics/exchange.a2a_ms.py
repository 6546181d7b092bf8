"""exchange.a2a_ms: device milliseconds per step of the ops the program
names ``stage{i}.a2a`` (every all-to-all of an exchange, the int8 scale
exchange included), per chip.  Nothing where no exchange ran."""


def read(r):
    s = getattr(r, "scopes", None)
    if s is None or s.kind_ops["a2a"] == 0:
        return None
    return s.kind_ns["a2a"] * 1e-6
