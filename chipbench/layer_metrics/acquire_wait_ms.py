"""Time the host is blocked on the acquisition sweeps, mean ms a request: the
stage span ``device.wait`` under the histogram label ``phase=acquire`` (the
75k-evaluation sweep programs and the reconditionings between their picks),
over the window's requests. Left out where the program has no such label."""

from chipbench.lib import device_half


def read(evidence):
    return device_half.wait_ms(evidence, "acquire")
