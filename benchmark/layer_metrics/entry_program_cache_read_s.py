"""entry.program_cache_read_s: the seconds the persistent compilation cache
took to hand back executables before the window: the sum of the program's
`jax.cache_read_seconds` histogram (obs/device.py compile watch) in the
registry snapshot taken where the window opens. These seconds are INSIDE
entry.program_compile_s (JAX reports a read as a compile); 0 where every
program was compiled anew. None for a program whose watch does not keep it."""


def read(ctx):
    return ctx.registry_before.get("jax.cache_read_seconds.sum")
