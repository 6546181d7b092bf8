"""exchange.realign_ms: device milliseconds per step of the exchanges'
local realignment, the ops the program names ``stage{i}.encode`` and
``stage{i}.decode`` (pack, codec and unpack before and after the
collective), per chip.  Nothing where no exchange ran."""


def read(r):
    s = getattr(r, "scopes", None)
    if s is None or s.kind_ops["encode"] + s.kind_ops["decode"] == 0:
        return None
    return (s.kind_ns["encode"] + s.kind_ns["decode"]) * 1e-6
