"""Set-up: process start to the end of the warm-up (imports, weights drawn
on the device, compiles or compile-cache loads, warm-up requests)."""


def read(run):
    return run.setup_s
