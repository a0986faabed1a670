"""Time the host is blocked on the chip, mean ms a request: the stage span
``device.wait`` — on the sequential path the two device phases of a suggest
(train, acquire), on the fused path one wait a flush, shared out over the
window's requests."""

from chipbench.lib import stages


def read(evidence):
    return stages.mean_ms_per_request(evidence, ("device.wait",))
