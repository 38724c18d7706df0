"""setup_s: seconds from the start of the process to the window's opening:
imports, parameter initialisation, compiling or loading every program,
both tiers' calibration, the prewarm and the warm-up traffic."""


def read(run):
    return run.setup_s
