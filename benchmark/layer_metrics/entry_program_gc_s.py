"""entry.program_gc_s: the seconds the interpreter's collector held the process
before the window, all generations: the program's `host.gc_pause_seconds` pull
gauge (obs/device.py compile watch, fed by its gc.callbacks entry) in the
registry snapshot taken where the window opens. None for a program whose watch
does not keep it."""


def read(ctx):
    return ctx.registry_before.get("host.gc_pause_seconds")
