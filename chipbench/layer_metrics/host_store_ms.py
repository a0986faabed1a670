"""Host time a suggest spends in the service and its datastore, mean ms a
request: the stage spans ``service.read`` (study fetch, open-trial claim, the
Pythia request) + ``policy.load_trials`` (every completed and active trial,
datastore → proto → pyvizier) + ``service.write`` (``create_trial`` × count,
metadata deltas, the operation)."""

from chipbench.lib import stages


def read(evidence):
    return stages.mean_ms_per_request(
        evidence, ("service.read", "policy.load_trials", "service.write")
    )
