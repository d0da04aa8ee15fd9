"""Share of the traced session in which the device idled and no
program span but the engine's whole-run ``run`` (or none at all)
covered the host: idle time the program's spans do not name, read on
the profiler's clock."""

from bench import scopes


def read(ctx):
    red = scopes.of(ctx)
    if red is None or not red.spanned or not red.window_s:
        return None
    return 100.0 * red.idle_unexplained_s / red.window_s
