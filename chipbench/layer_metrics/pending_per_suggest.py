"""ACTIVE trials a computation conditioned on as pending, a suggest:
``serving_stats()`` pending_trials_conditioned ÷ suggest_turns over the
window. A reading of the traffic (how many handed-out trials were still out
when a turn began), and a guard: 0 over a whole window of a shared study
says the conditioning was lost."""


def read(evidence):
    stats = evidence["stats_window"]
    turns = stats.get("suggest_turns", 0)
    return stats.get("pending_trials_conditioned", 0) / turns if turns else None
