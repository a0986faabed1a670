"""Share of the window's suggest requests that found their study's fit cached
and trained nothing (``serving_stats()`` cached_fit_suggests ÷ requests), in
%: no trial of the study was completed since its last train, so the suggest
is one sweep on the sequential path and cannot join a fused flush. Left out
where the program has no such counter."""


def read(evidence):
    cached, requests = evidence["stats_window"].get("cached_fit_suggests"), evidence.get("attempted")
    return 100.0 * cached / requests if cached is not None and requests else None
