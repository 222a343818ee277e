"""Host-blocking seconds a step spends copying the gradient to the host:
per window step, each worker's ``pack`` span seconds (the device -> host
copy of every gradient leaf, which first waits for the device to finish
the backward, and their concatenation); the most of any worker; the mean
over the window's steps (the reduction of ``sync_s_per_step``)."""
from chipbench.hostwork import seconds_per_step


def read(run):
    return seconds_per_step(run, "pack")
