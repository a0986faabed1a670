"""Time the host is blocked on the ARD train program, mean ms a request: the
stage span ``device.wait`` under the histogram label ``phase=train`` — the
sequential and mesh paths block on the trained states before the sweeps are
launched — over the window's requests, so that with ``acquire_wait_ms`` it
adds up to ``device_wait_ms`` where no flush is fused. Left out where the
program has no such label."""

from chipbench.lib import device_half


def read(evidence):
    return device_half.wait_ms(evidence, "train")
