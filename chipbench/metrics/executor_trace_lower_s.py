"""Seconds JAX spends tracing the grid call to a jaxpr and lowering it to
MLIR (``/jax/core/compile/jaxpr_trace_duration`` +
``jaxpr_to_mlir_module_duration``), on a grid call made after the
in-memory caches are dropped, as the warm-up call finds them (host clock,
``chipbench/program_trace.py``). The part of set-up that only a program
change cuts; a persistent-cache read or backend compile is not in it."""

from chipbench import program_trace

LAYER = "executor (core.runner cache, jax.jit)"
UNIT = "s"
MOVES = "setup_s"


def read(ctx):
    return program_trace.measure(ctx).get("trace_lower_s")
