"""From the process's start to the window's: imports, weights, inputs,
the program's build and every compile or cache load of warm-up."""


def read(run):
    return run.setup_s
