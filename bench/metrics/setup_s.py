"""setup_s: process start to the first timed round: imports, data and
weights, the deployment, compilation and the warm-up rounds."""


def read(run):
    return run.setup_s
