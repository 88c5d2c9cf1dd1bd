"""Set-up time: process start to the window's opening (imports, weights,
building shell, gateway and engine, warming every shape; compiling too
where the cache is cold).  Host clock."""


def read(run):
    return run.setup_s
