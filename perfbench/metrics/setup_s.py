"""Process start to window start: imports, device start-up, stream and
weight generation, the engine, compile-cache reads, the warm-up replay and
the warm-up of every shape.  The engine-state save that the reference
comparison needs is not counted."""


def read(ctx):
    return float(ctx["setup_s"])
