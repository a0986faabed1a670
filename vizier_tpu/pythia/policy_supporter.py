"""PolicySupporter: the algorithm's read-back channel to the study DB.

Parity with ``/root/reference/vizier/_src/pythia/policy_supporter.py:26-133``.
"""

from __future__ import annotations

import abc
import datetime
from typing import Collection, Iterable, List, Optional, Tuple

from vizier_tpu.pythia import errors
from vizier_tpu.pyvizier import study_config as sc
from vizier_tpu.pyvizier import trial as trial_


class PolicySupporter(abc.ABC):
    """Reads study state on behalf of a running policy."""

    @abc.abstractmethod
    def GetStudyConfig(self, study_guid: Optional[str] = None) -> sc.StudyConfig:
        """Fetches a study's config (defaults to the policy's own study)."""

    @abc.abstractmethod
    def GetTrials(
        self,
        *,
        study_guid: Optional[str] = None,
        trial_ids: Optional[Iterable[int]] = None,
        min_trial_id: Optional[int] = None,
        max_trial_id: Optional[int] = None,
        status_matches: Optional[trial_.TrialStatus] = None,
        include_intermediate_measurements: bool = True,
    ) -> List[trial_.Trial]:
        """Fetches trials matching the filters."""

    def GetTrialDelta(
        self, held_ids: Collection[int]
    ) -> Tuple[List[trial_.Trial], List[trial_.Trial], int]:
        """What a holder of the completed trials ``held_ids`` still lacks.

        ``(new_completed, active, num_completed)``: the completed trials
        not held, every ACTIVE trial, and the completed trials counted,
        held or new (so ``num_completed - len(new_completed)`` is what the
        holder reuses, never negative). The default reads everything once;
        a supporter with a cheaper read behind it overrides this.
        """
        trials = self.GetTrials()
        completed = [t for t in trials if t.status == trial_.TrialStatus.COMPLETED]
        return (
            [t for t in completed if t.id not in held_ids],
            [t for t in trials if t.status == trial_.TrialStatus.ACTIVE],
            len(completed),
        )

    def CheckCancelled(self, note: str = "") -> None:
        """Raises CancelComputeError if the RPC was cancelled (default: no-op)."""

    def TimeRemaining(self) -> datetime.timedelta:
        """Time left before the deadline (default: unbounded)."""
        return datetime.timedelta.max

    def SendMetadata(self, delta: trial_.MetadataDelta) -> None:
        """Persists metadata immediately (mid-computation checkpointing)."""
        raise NotImplementedError(f"{type(self).__name__} does not support SendMetadata.")
