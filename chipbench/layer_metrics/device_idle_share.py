"""Share of the traced span in which no operation ran on the device, in %."""

from chipbench.lib import trace_reduce


def read(evidence):
    return trace_reduce.idle_share(evidence["trace"])
