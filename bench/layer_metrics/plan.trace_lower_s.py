"""plan.trace_lower_s: seconds tracing to a jaxpr and lowering to StableHLO,
outside XLA's compile, as the program's compile recorder
(``repro.core.spans``) counts them for the whole process up to the read:
in a traced run, the set-up's plan, inputs and warm-up, and any other
jit.  Nothing where the program has no recorder or it saw no compile."""


def read(_r):
    try:
        from repro.core import spans
    except ImportError:
        return None
    c = spans.compile_totals()
    if c["xla_compiles"] + c["cache_hits"] == 0:
        return None
    return c["trace_lower_s"]
