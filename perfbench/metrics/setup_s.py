"""Process start to the first measured block (or the window's opening):
imports, bf.init, weights, compilation or cache loads, warm-up, ramp."""


def read(run):
    return run["setup_s"]
