"""Bytes of gradient through the host per step: the ``nbytes`` of every
``pack`` span (the host vector copied off the device) and ``update`` span
(the vector copied back), summed over workers, over the window's steps.
With fp32 gradients it is 8 bytes per parameter of the model."""
from chipbench.hostwork import bytes_per_step


def read(run):
    return bytes_per_step(run)
