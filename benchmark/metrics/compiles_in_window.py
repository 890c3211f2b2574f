"""Backend compilations (JAX's ``backend_compile_duration`` events)
that began inside the traced window; reads 0 when the warm-up touched
every shape the window used."""


def read(ctx):
    return ctx.get("compiles_in_window")
