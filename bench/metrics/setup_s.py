"""setup_s: process start to the first due message of the window,
loading, data generation, warm-up and compilation included (host clock)."""


def read(run):
    return run.setup_s
