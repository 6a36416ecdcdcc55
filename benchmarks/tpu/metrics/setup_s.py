"""Set-up: process start to the first timed request (imports, TPU
start-up, weights, traffic pool, compiling or loading every bucket the
traffic reaches, warm-up traffic). Host clock."""


def read(run):
    return run.setup_s
