"""Share of the chip's bf16 peak that the traffic's required FLOPs fill over
the window (``flops.py`` counts them from the requests and the config, not
from what the program computes)."""


def read(run):
    return run.mfu_percent()
