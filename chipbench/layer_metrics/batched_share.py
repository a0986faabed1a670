"""Share of the window's suggest requests that were served from a fused,
cross-study flush (``serving_stats()`` batched_suggests over the window ÷
requests), in %. The rest were handed back to the sequential designer."""


def read(evidence):
    return evidence.get("batched_share_pct")
