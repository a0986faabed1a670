"""Device busy time per suggest, in ms — an estimate: the share of the
traced span (1 s) in which an operation ran on the device, times the
window's seconds per completed suggest request. Requests can be longer than
the span, so it is a busy share times a rate, not a sum over requests."""

from chipbench.lib import trace_reduce


def read(evidence):
    return trace_reduce.busy_ms_per_request(
        evidence["trace"], evidence["seconds"], evidence["completed_in_window"]
    )
