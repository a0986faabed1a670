"""``closed_rounds``' workers on studies that have passed the sparse switch.

The worker loop, the set-up and the supply arithmetic are
``closed_rounds``' own (``Generator``, ``study_count``,
``requests_after_setup``: imported, not copied); the parameters are its
traffic file's. What differs is the rule of the data, which
``closed_rounds.check_data`` states for the exact side alone (one bucket
under 512 completed trials). Here every study lives in ONE padding bucket
ABOVE the switch — the configuration's ``trial_padding_bucket``, 1,024 —
from its first suggest to its last, so that no request of a run is served
by the exact programs and none compiles a shape the set-up has not met.
"""

from __future__ import annotations

from typing import Any, Dict

from chipbench.generators.closed_rounds import Generator, requests_after_setup, study_count  # noqa: F401
from chipbench.lib import studies as studies_lib

SPARSE_SWITCH = 512  # vizier_tpu/surrogates/config.py sparse_threshold_trials, as shipped


def check_data(config: Dict[str, Any], traffic: Dict[str, Any]) -> None:
    """This generator's rules for a cell's files; an AssertionError says
    which one they break."""
    start, count = traffic["start_trials"], traffic["suggest_count"]
    assert config["surrogate"] == "sparse", (
        f"the configuration's surrogate is {config['surrogate']!r}; this generator's studies are sparse")
    assert start >= SPARSE_SWITCH, (
        f"a study that starts at {start} trials is served by the exact programs "
        f"until it reaches the sparse switch at {SPARSE_SWITCH}")
    home = studies_lib.bucket(start, count)
    assert home[0] == config["trial_padding_bucket"], (
        f"a study that starts at {start} trials trains in the {home[0]} bucket, "
        f"not the configuration's trial_padding_bucket {config['trial_padding_bucket']}")
    rounds = studies_lib.rounds_in_bucket(start, count)
    last = start + (rounds - 1) * count  # completed trials at the last suggest
    assert studies_lib.bucket(last, count) == home and studies_lib.bucket(last + count, count) != home
    assert last == config["completed_trials"], (
        f"a study's last suggest holds {last} completed trials before it would leave the "
        f"{home[0]} bucket, not the configuration's completed_trials {config['completed_trials']}")
    served = studies_lib.rounds_in_bucket(start, count, traffic.get("max_rounds_per_study"))
    assert served >= 9, (
        f"a study makes {served} rounds before it is retired; a window needs 9")
