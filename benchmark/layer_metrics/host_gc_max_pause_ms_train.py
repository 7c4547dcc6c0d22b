"""host.gc_max_pause_ms.train: the longest pause of the interpreter's collector
that STARTED inside the window, from the ring the program's compile watch
(obs/device.py) keeps of pauses over a millisecond: 0 where none passed that
floor. The window's two ends are the runner's own clock readings
(`t_window_start`, `window_s`), which is the clock the ring is stamped on
(time.perf_counter). None for a program whose watch keeps no such ring."""


def read(ctx):
    try:
        from yet_another_mobilenet_series_tpu.obs.device import install_compile_watch
    except ImportError:
        return None
    longest = getattr(install_compile_watch(), "gc_max_pause_between", None)
    if longest is None:
        return None
    t0 = ctx.result["t_window_start"]
    return 1e3 * longest(t0, t0 + ctx.result["facts"]["window_s"])
