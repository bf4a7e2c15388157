"""``compiles_in_window``: backend compile events
(``/jax/core/compile/backend_compile_duration``) between the start and the
end of the measured window; every shape should be warm by then."""


def read(ctx):
    return ctx.compiles_in_window
