"""Share of a study's completed trials that a suggest did not read again,
in %: ``serving_stats()`` trials_reused / (trials_reused + trials_fetched)
over the window. The guard on the policy's delta read (PR 26): the cached
designer already holds the reused ones, and only the fetched ones were
converted from the datastore. About 93 where 25 trials are new of ~375; a
policy that reads the whole study again reads 0."""


def read(evidence):
    stats = evidence["stats_window"]
    reused, fetched = stats.get("trials_reused", 0), stats.get("trials_fetched", 0)
    return 100.0 * reused / (reused + fetched) if reused + fetched else None
