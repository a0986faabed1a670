"""PolicySupporter reading trials back from the Vizier service.

Parity with ``/root/reference/vizier/_src/service/service_policy_supporter.py``.

Converting a trial proto to a pyvizier ``Trial`` costs about fifteen times
what copying it does, so every filter is decided on the **proto** and only
the trials that pass are converted. Against the in-process
``VizierServicer`` the reads are its copy-free ones (``trial_frontier``,
``read_trials``: ids and states, by-name gets, the storage-level state
filter); a remote stub offers only the RPC surface, so there it is one
``ListTrials`` filtered the same way. Both return what converting the
whole study and filtering afterwards returned.
"""

from __future__ import annotations

from typing import Collection, Iterable, List, Optional, Tuple

from vizier_tpu import pyvizier as vz
from vizier_tpu.pythia import policy_supporter
from vizier_tpu.service import proto_converters as pc
from vizier_tpu.service.protos import study_pb2, vizier_service_pb2

_State = study_pb2.Trial

# Storage-level prefilter per status: every state whose proto CAN convert
# to that status. SUCCEEDED without a final measurement converts to an
# ACTIVE trial, so ACTIVE keeps SUCCEEDED rows and `_status` decides: a
# `GetTrials(status_matches=ACTIVE)` still copies the study's SUCCEEDED
# protos (a copy costs a fifteenth of a conversion) and does not use a
# store's open-trial index. The service writes no such row, but a
# store may hold one, and the callers of `GetTrials` are promised the
# lists that converting everything gave. The serving policy's read is
# `GetTrialDelta`, which goes by the frontier instead.
_CANDIDATE_STATES = {
    vz.TrialStatus.COMPLETED: (_State.SUCCEEDED, _State.INFEASIBLE),
    vz.TrialStatus.STOPPING: (_State.STOPPING,),
    vz.TrialStatus.REQUESTED: (_State.REQUESTED,),
    vz.TrialStatus.ACTIVE: (
        _State.ACTIVE, _State.STATE_UNSPECIFIED, _State.SUCCEEDED
    ),
}

# The states `VizierServicer.trial_frontier` lists.
_FRONTIER_STATES = (_State.SUCCEEDED, _State.INFEASIBLE, _State.ACTIVE)


def _status(proto: study_pb2.Trial) -> vz.TrialStatus:
    """``pc.trial_from_proto(proto).status``, without the conversion."""
    if proto.state == _State.INFEASIBLE or (
        proto.state == _State.SUCCEEDED and proto.HasField("final_measurement")
    ):
        return vz.TrialStatus.COMPLETED
    if proto.state == _State.STOPPING:
        return vz.TrialStatus.STOPPING
    if proto.state == _State.REQUESTED:
        return vz.TrialStatus.REQUESTED
    return vz.TrialStatus.ACTIVE


class ServicePolicySupporter(policy_supporter.PolicySupporter):
    """Reads study/trial state via the Vizier servicer (or stub)."""

    def __init__(self, study_name: str, vizier_service):
        self._study_name = study_name
        self._vizier = vizier_service
        # The in-process servicer's by-id / by-state read; None behind a
        # stub (the test PythiaServicer._trial_frontier makes).
        self._read_trials = getattr(vizier_service, "read_trials", None)

    def GetStudyConfig(self, study_guid: Optional[str] = None) -> vz.StudyConfig:
        name = study_guid or self._study_name
        study = self._vizier.GetStudy(vizier_service_pb2.GetStudyRequest(name=name))
        return pc.study_config_from_proto(study.study_spec)

    def _list_protos(self, name: str) -> Iterable[study_pb2.Trial]:
        return self._vizier.ListTrials(
            vizier_service_pb2.ListTrialsRequest(parent=name)
        ).trials

    def GetTrials(
        self,
        *,
        study_guid: Optional[str] = None,
        trial_ids: Optional[Iterable[int]] = None,
        min_trial_id: Optional[int] = None,
        max_trial_id: Optional[int] = None,
        status_matches: Optional[vz.TrialStatus] = None,
        include_intermediate_measurements: bool = True,
    ) -> List[vz.Trial]:
        name = study_guid or self._study_name
        ids = frozenset(trial_ids) if trial_ids is not None else None
        if self._read_trials is None:
            protos = self._list_protos(name)
        elif ids is not None:
            protos = self._read_trials(name, trial_ids=ids)
        else:
            protos = self._read_trials(
                name, states=_CANDIDATE_STATES.get(status_matches)
            )
        out = []
        for proto in protos:
            if ids is not None and proto.id not in ids:
                continue
            if min_trial_id is not None and proto.id < min_trial_id:
                continue
            if max_trial_id is not None and proto.id > max_trial_id:
                continue
            if status_matches is not None and _status(proto) != status_matches:
                continue
            out.append(pc.trial_from_proto(proto))
        return out

    def GetTrialDelta(
        self, held_ids: Collection[int]
    ) -> Tuple[List[vz.Trial], List[vz.Trial], int]:
        """See the base class; nothing but the two lists is converted.

        The lists are the ones ``GetTrials(status_matches=COMPLETED)``
        minus the held ids and ``GetTrials(status_matches=ACTIVE)`` would
        give. In-process: the frontier as ids, a set difference (not a
        high-water mark: trial 7 may complete after trial 9), then by-name
        gets, or one listing where most of the study is missing (a replay).
        A trial deleted between frontier and fetch is skipped; one that
        completes between them is ACTIVE to the frontier and completed once
        fetched, so it is handed over as new and counted as new, whatever
        ``held_ids`` holds. (The frontier does not list STATE_UNSPECIFIED,
        which the service never stores: ``CreateTrial`` makes it ACTIVE.)
        """
        name = self._study_name
        completed = vz.TrialStatus.COMPLETED
        if self._read_trials is None:
            protos = self._list_protos(name)
            num_reused = sum(
                p.id in held_ids and _status(p) == completed for p in protos
            )
        else:
            completed_ids, active_ids, _ = self._vizier.trial_frontier(name)
            missing = [i for i in completed_ids if i not in held_ids]
            num_reused = len(completed_ids) - len(missing)
            if 2 * len(missing) > len(completed_ids):
                protos = self._read_trials(name, states=_FRONTIER_STATES)
            else:
                protos = self._read_trials(name, trial_ids=missing + active_ids)
        new_completed, active = [], []
        for proto in protos:
            status = _status(proto)
            if status == completed and proto.id not in held_ids:
                new_completed.append(pc.trial_from_proto(proto))
            elif status == vz.TrialStatus.ACTIVE:
                active.append(pc.trial_from_proto(proto))
        return new_completed, active, num_reused + len(new_completed)
