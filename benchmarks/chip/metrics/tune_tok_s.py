"""Training tokens of the steps completed in the window, the step running
at the close by its share inside the window, over the window."""


def read(ctx):
    return ctx["tokens"] / ctx["window_s"]
