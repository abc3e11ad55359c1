"""Seconds JAX spent tracing, lowering and compiling (or fetching from the
persistent cache) during set-up, from its monitoring events."""


def read(rec):
    return rec.get("compile_s")
