"""plan.compile_s: host-clock seconds of the cell's plan build plus the
ahead-of-time ``lower().compile()`` of its timed executables (a load from
the persistent cache in a warm run)."""


def read(r):
    return r.spans_s.get("plan.compile")
