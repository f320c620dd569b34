"""Seconds from the start of set-up (compile cache, weights, engine, set-up
traffic through two window boundaries) until the window opens."""


def read(run):
    return run.setup_s
