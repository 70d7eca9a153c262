"""Process start to the first timed query: imports, device start, the
warm-up's table builds and compiles, s."""


def read(run):
    return run.setup_s
