"""entry.program_lower_s: the seconds the host spent lowering traced programs
to MLIR modules before the window: the sum of the program's
`jax.lower_seconds` histogram (obs/device.py compile watch) in the registry
snapshot taken where the window opens, as entry_program_compile_s.py reads its
sum. None for a program whose watch does not keep it."""


def read(ctx):
    return ctx.registry_before.get("jax.lower_seconds.sum")
