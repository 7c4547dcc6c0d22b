"""entry.program_compile_s: the seconds of backend compilation the PROGRAM's
own compile watch (obs/device.py, installed by utils/compile_cache.configure)
had counted when the window opened: the sum of its
`jax.backend_compile_seconds` histogram in the registry snapshot taken there.
The registry readers take a window's difference; set-up is everything before
it, so this one reads the snapshot itself. Measured inside the program, beside
entry.compile_s, which the benchmark's own listener measures from outside.
None for a program without the watch."""


def read(ctx):
    return ctx.registry_before.get("jax.backend_compile_seconds.sum")
