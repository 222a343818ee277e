"""Host-blocking seconds a step spends applying the reduced gradient: per
window step, each worker's ``update`` span seconds (the division by the
replica count, the host -> device copy, the dispatch of the optimizer
step); the most of any worker; the mean over the window's steps.  The span
ends once the optimizer's ops are dispatched, not once the device has run
them: device work left running shows up in the next blocking op."""
from chipbench.hostwork import seconds_per_step


def read(run):
    return seconds_per_step(run, "update")
