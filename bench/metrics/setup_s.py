"""setup_s: seconds from process start to the start of the measured
window: weights, engine and residency store, compiles or cache loads,
warm-up and the mix's lead-in."""


def read(run):
    return run.setup_s
