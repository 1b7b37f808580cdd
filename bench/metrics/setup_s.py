"""setup_s: process start to window start (data, build, warm-up, compile
or cache loads)."""


def read(run):
    return run.setup_s
