"""Mean time a suggest held its study's turn, in ms: Σ ÷ count of
``vizier_study_turn_seconds`` over the window — claim, Pythia, the write.
Its inverse is the most suggestions a second one study can be given."""

from chipbench.lib import reduce


def read(evidence):
    hist = evidence["histograms_window"].get("vizier_study_turn_seconds")
    if hist is None:
        return None
    _, count, total = reduce.pooled(hist)
    return total / count * 1e3 if count else None
