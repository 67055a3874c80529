"""Mean host time of a stage call outside its device program: padding,
stacking, the transfer in, dispatch and reading the answer back."""

from bench.record import host_ms


def read(run):
    return host_ms(run)
