"""setup_s: seconds from process start to the opening of the measured
window: imports, weights, engine warm-up (compiles or cache loads) and the
traffic's warm-up to steady state."""


def read(run):
    return run.setup_s
