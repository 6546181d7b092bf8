"""fft.xform_ms: device milliseconds per step of the ops the program names
``stage{i}.xform`` (the 1-D transforms proper, with the transposes a
transform along a leading axis makes), per chip.  Nothing where the
program names no transform."""


def read(r):
    s = getattr(r, "scopes", None)
    if s is None or s.kind_ops["xform"] == 0:
        return None
    return s.kind_ns["xform"] * 1e-6
