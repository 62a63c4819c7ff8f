"""Seconds of set-up spent tracing, lowering, compiling and reading
programs from the persistent cache (JAX's monitoring events)."""


def read(rec):
    return rec["setup"]["compile_s"]
