"""A duration the harness took itself before the window: ``mark`` names it
(layer_s, backend_s, compile_s, first_diff_s)."""


def read(ctx, mark):
    return ctx["clock"].get(mark)
