"""setup_s: seconds from process start to the start of the window: data,
pool radii, index build and warm-up (compilation, or loading it from the
persistent cache)."""


def read(run):
    return run.setup_s
