"""Share of the window's batch-executor flushes whose bucket is the fused
sparse program's, in %: of the flushes ``vizier_batch_occupancy`` counted
(one series a bucket label, ``<kind>/t<pad>/f<cont>x<cat>/m<metrics>/q<count>``;
``bucket`` is a series' first label, the registry sorts them), those whose
kind is ``gp_ucb_pe_sparse``. A guard, like ``mesh_suggest_share``: under 100
a flush of the cell ran another program (a study under the sparse switch met
the exact one) and the cell's number is of something else. Nothing from a
program without the labelled series, or a window without a flush."""

from chipbench.lib import reduce

SPARSE_KIND = "gp_ucb_pe_sparse"


def read(evidence):
    hist = evidence.get("histograms_window", {}).get("vizier_batch_occupancy")
    if hist is None:
        return None
    _, flushes, _ = reduce.pooled(hist, "bucket=")
    _, sparse, _ = reduce.pooled(hist, f"bucket={SPARSE_KIND}/")
    return 100.0 * sparse / flushes if flushes else None
