"""``ServicePolicySupporter.GetTrials``: filters decided on the proto.

Whatever the filters, and whether the Vizier object is the in-process
servicer or shows only its RPC surface (a stub), the supporter returns the
list that converting the whole study and filtering afterwards returns.
"""

import itertools

import pytest

from vizier_tpu import pyvizier as vz
from vizier_tpu.service import proto_converters as pc
from vizier_tpu.service import service_policy_supporter, vizier_service
from vizier_tpu.service.protos import study_pb2, vizier_service_pb2

STUDY = "owners/o/studies/s"
OTHER = "owners/o/studies/other"
_State = study_pb2.Trial


class RpcSurface:
    """A Vizier object as a remote stub shows it: the RPC methods alone."""

    def __init__(self, servicer):
        self._servicer = servicer

    def __getattr__(self, name):
        if not name[0].isupper():
            raise AttributeError(name)
        return getattr(self._servicer, name)


def _trial(study, trial_id, state, final=None, measurements=0):
    proto = _State(name=f"{study}/trials/{trial_id}", id=trial_id, state=state)
    proto.parameters.add(name="x").value.double_value = 0.1 * trial_id
    for step in range(measurements):
        m = proto.measurements.add(steps=step + 1)
        m.metrics.add(name="obj", value=float(step))
    if final is not None:
        proto.final_measurement.metrics.add(name="obj", value=final)
    if state == _State.INFEASIBLE:
        proto.infeasibility_reason = "diverged"
    if state == _State.STOPPING:
        proto.stopping_reason = "plateau"
    proto.creation_time_secs = 1.7e9 + trial_id
    if state in (_State.SUCCEEDED, _State.INFEASIBLE):
        proto.completion_time_secs = 1.7e9 + 100 + trial_id
    return proto


@pytest.fixture(scope="module")
def servicer():
    servicer = vizier_service.VizierServicer()
    config = vz.StudyConfig(algorithm="RANDOM_SEARCH")
    config.search_space.root.add_float_param("x", 0.0, 1.0)
    config.metric_information.append(
        vz.MetricInformation(name="obj", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
    )
    for name in (STUDY, OTHER):
        servicer.CreateStudy(
            vizier_service_pb2.CreateStudyRequest(
                parent="owners/o", study=pc.study_to_proto(config, name)
            )
        )
    # Written straight to the datastore, so that states the service itself
    # never stores (8: SUCCEEDED with no final measurement, 9: unspecified)
    # are held to the same rule.
    states = [
        (1, _State.SUCCEEDED, 1.0), (2, _State.ACTIVE, None),
        (3, _State.INFEASIBLE, None), (4, _State.REQUESTED, None),
        (5, _State.STOPPING, None), (6, _State.SUCCEEDED, 2.0),
        (7, _State.ACTIVE, None), (8, _State.SUCCEEDED, None),
        (9, _State.STATE_UNSPECIFIED, None), (10, _State.INFEASIBLE, 0.5),
    ]
    for trial_id, state, final in states:
        servicer.datastore.create_trial(
            _trial(STUDY, trial_id, state, final, measurements=trial_id % 3)
        )
    servicer.datastore.create_trial(_trial(OTHER, 1, _State.SUCCEEDED, 3.0))
    return servicer


def _convert_all_then_filter(servicer, study, ids, lo, hi, status):
    trials = [
        pc.trial_from_proto(p)
        for p in servicer.ListTrials(
            vizier_service_pb2.ListTrialsRequest(parent=study)
        ).trials
    ]
    return [
        t for t in trials
        if (ids is None or t.id in set(ids))
        and (lo is None or t.id >= lo)
        and (hi is None or t.id <= hi)
        and (status is None or t.status == status)
    ]


STATUSES = [None] + list(vz.TrialStatus)
ID_SETS = {"any": None, "some": (2, 3, 6, 8, 99), "none": ()}
RANGES = {"open": (None, None), "min": (3, None), "max": (None, 8), "both": (2, 6)}


@pytest.mark.parametrize("surface", ["in_process", "stub"])
@pytest.mark.parametrize(
    "status,ids,span",
    list(itertools.product(STATUSES, sorted(ID_SETS), sorted(RANGES))),
    ids=lambda v: getattr(v, "name", None) or str(v),
)
def test_get_trials_equals_convert_all_then_filter(
    servicer, surface, status, ids, span
):
    vizier = servicer if surface == "in_process" else RpcSurface(servicer)
    supporter = service_policy_supporter.ServicePolicySupporter(STUDY, vizier)
    lo, hi = RANGES[span]
    got = supporter.GetTrials(
        trial_ids=ID_SETS[ids], min_trial_id=lo, max_trial_id=hi,
        status_matches=status,
    )
    want = _convert_all_then_filter(servicer, STUDY, ID_SETS[ids], lo, hi, status)
    assert [t.id for t in got] == [t.id for t in want]
    assert got == want  # every field of every trial


def test_the_cases_cover_every_status(servicer):
    by_status = {
        status: _convert_all_then_filter(servicer, STUDY, None, None, None, status)
        for status in vz.TrialStatus
    }
    assert [t.id for t in by_status[vz.TrialStatus.COMPLETED]] == [1, 3, 6, 10]
    assert [t.id for t in by_status[vz.TrialStatus.ACTIVE]] == [2, 7, 8, 9]
    assert [t.id for t in by_status[vz.TrialStatus.REQUESTED]] == [4]
    assert [t.id for t in by_status[vz.TrialStatus.STOPPING]] == [5]


@pytest.mark.parametrize("surface", ["in_process", "stub"])
@pytest.mark.parametrize("ids", [None, (1, 2)], ids=["listing", "by_id"])
def test_study_guid_and_missing_study(servicer, surface, ids):
    vizier = servicer if surface == "in_process" else RpcSurface(servicer)
    supporter = service_policy_supporter.ServicePolicySupporter(STUDY, vizier)
    other = supporter.GetTrials(study_guid=OTHER, trial_ids=ids)
    assert [t.id for t in other] == [1]
    assert other[0].final_measurement.metrics["obj"].value == 3.0
    with pytest.raises(KeyError):  # NotFoundError, as ListTrials raises it
        supporter.GetTrials(study_guid="owners/o/studies/absent", trial_ids=ids)


@pytest.mark.parametrize("surface", ["in_process", "stub"])
@pytest.mark.parametrize(
    "held", [(), (1, 3), (1, 3, 6, 10), (1, 3, 6, 10, 77)],
    ids=["none", "some", "all", "one_since_deleted"],
)
def test_trial_delta_equals_the_two_filtered_listings(servicer, surface, held):
    vizier = servicer if surface == "in_process" else RpcSurface(servicer)
    supporter = service_policy_supporter.ServicePolicySupporter(STUDY, vizier)
    completed = _convert_all_then_filter(
        servicer, STUDY, None, None, None, vz.TrialStatus.COMPLETED
    )
    active = _convert_all_then_filter(
        servicer, STUDY, None, None, None, vz.TrialStatus.ACTIVE
    )
    if surface == "in_process":
        # The frontier reads states alone and the service never stores
        # STATE_UNSPECIFIED (CreateTrial makes it ACTIVE): trial 9 is the
        # one trial the in-process delta does not see.
        active = [t for t in active if t.id != 9]
    new_completed, got_active, num_completed = supporter.GetTrialDelta(set(held))
    assert new_completed == [t for t in completed if t.id not in held]
    assert got_active == active
    # Trial 8 (SUCCEEDED, no final measurement) is completed to the
    # frontier and ACTIVE once converted: handed over as ACTIVE and counted
    # as neither held nor new, on both surfaces.
    assert num_completed == 4
