"""Host time a suggest spends on the designer's side of the device, mean ms
a request: the stage spans ``designer.update`` (new trials into the
designer) + ``designer.prepare`` (encode, padding, RNG before the device can
start) + ``designer.decode`` (device results → suggestions; a fused flush's
one demux is shared out over the window's requests)."""

from chipbench.lib import stages


def read(evidence):
    return stages.mean_ms_per_request(
        evidence, ("designer.update", "designer.prepare", "designer.decode")
    )
