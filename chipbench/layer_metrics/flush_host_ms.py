"""Host work of one fused flush around its device program, mean ms a flush:
the per-flush stage spans ``flush.stack`` (re-stack and upload of every
member) + ``designer.decode`` (the one fetch and demux), ÷ flushes."""

from chipbench.lib import stages


def read(evidence):
    found = stages.series(evidence)
    if found is None:
        return None
    flushes = found.get(("flush.stack", stages.PER_FLUSH), (0, 0.0))[0]
    total = stages.seconds(found, ("flush.stack", "designer.decode"), stages.PER_FLUSH)
    if flushes == 0 or total is None:
        return None
    return total / flushes * 1e3
