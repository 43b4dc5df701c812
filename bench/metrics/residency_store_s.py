"""residency_store_s: seconds the engine spent building the weight
arena's host store (`ServingEngine.residency_build_s`)."""


def read(run):
    return run.residency_build_s
