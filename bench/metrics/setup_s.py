"""Set-up seconds: process start to the window's start (weights, pool,
warm-up and every compile), host clock."""


def read(run):
    return run.setup_s
