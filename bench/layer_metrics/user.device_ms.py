"""user.device_ms: device milliseconds per step of the ops outside every
``pfft.*`` scope of the program (the user's own work around the plan:
products, projection, the time-step update), per chip.  Nothing where the
program names none of its work."""


def read(r):
    s = getattr(r, "scopes", None)
    if s is None or s.scoped_ops == 0:
        return None
    return s.user_ns * 1e-6
