"""Picks of a sparse suggest that joined its inducing set
(``serving_stats()`` nystrom_augments ÷ sparse_suggests over the window):
of a ``suggest(25)``'s picks, those whose Nyström residual under the trained
128 rows was over a tenth of the prior variance, each one row more in every
later reconditioning of the batch. A reading of the fit: ~0 where the
length scales are long enough for the trained rows to explain a pick, ~25
where they explain nothing. Nothing from a program without the counter (a
parent commit) or a window without a sparse suggest."""


def read(evidence):
    stats = evidence.get("stats_window", {})
    augments, suggests = stats.get("nystrom_augments"), stats.get("sparse_suggests")
    return augments / suggests if augments is not None and suggests else None
