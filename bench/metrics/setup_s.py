"""setup_s: from the start of the process to the window's opening:
imports, device, weights, engine, warm-up compiles, server start."""


def read(run):
    return run.setup_s
