"""Set-up seconds: process start to the first measured step, host clock.

Imports, the device's start, weights or data made from the seed, and
every warm-up and compile the window's programs need.
"""


def read(run):
    return run.setup_s
