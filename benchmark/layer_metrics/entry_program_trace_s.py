"""entry.program_trace_s: the seconds the host spent TRACING jitted functions
before the window: the sum of the program's `jax.trace_seconds` histogram
(obs/device.py compile watch: outermost traces only, so the sum is wall time)
in the registry snapshot taken where the window opens, as
entry_program_compile_s.py reads its sum. None for a program whose watch does
not keep it."""


def read(ctx):
    return ctx.registry_before.get("jax.trace_seconds.sum")
