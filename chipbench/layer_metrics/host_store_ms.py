"""Host time a suggest spends in the service and its datastore, mean ms a
request: the stage spans ``service.read`` (study fetch, open-trial claim, the
Pythia request) + ``policy.load_trials`` (a delta read since PR 26: the
study's frontier as ids, then only the trials the cached designer lacks,
datastore → proto → pyvizier; ``trial_reuse_share`` guards that it engages)
+ ``service.write`` (``create_trial`` × count, metadata deltas, the
operation)."""

from chipbench.lib import stages


def read(evidence):
    return stages.mean_ms_per_request(
        evidence, ("service.read", "policy.load_trials", "service.write")
    )
