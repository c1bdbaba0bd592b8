"""Executables built inside the measured window: the AOT registry's
builds (compiled or loaded from its store) and jax's own backend builds,
the larger of the two. Must read 0; ``correct`` is false otherwise."""

NAME = "compiles_in_window"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "crypto.tpu.aot"
MOVES = "verdict_p50_ms"


def read(before: dict, after: dict, trace):
    return after["bench"]["builds_in_window"]
