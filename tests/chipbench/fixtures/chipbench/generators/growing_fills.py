"""A fixture for the contract tests: the interface of an arrival process
whose studies start empty and grow through the padding buckets, with the
rule such a generator owes in place of ``closed_rounds``'s one-bucket rule.
No run executes it.

Parameters (the traffic file): ``workers``, ``studies`` (filled one after
another, each shared by every worker), ``trials_per_study``,
``suggest_count``. The configuration lists the trained pads its set-up warms
up, ``trial_padding_buckets``: the window may meet no other.
"""

from chipbench.lib import studies as studies_lib


def study_count(traffic):
    return traffic["studies"]


def buckets_met(traffic):
    """The trained pads of every suggest from an empty study to a full one."""
    count = traffic["suggest_count"]
    sizes = range(0, traffic["trials_per_study"], count)
    return sorted({studies_lib.bucket(n, count)[0] for n in sizes})


def check_data(config, traffic):
    met, warmed = buckets_met(traffic), config["trial_padding_buckets"]
    assert met == warmed, (
        f"a study filled to {traffic['trials_per_study']} trials meets the buckets {met}; "
        f"the configuration's set-up warms up {warmed}")


class Generator:
    def __init__(self, server, config, traffic, seed, annotate):
        raise NotImplementedError("a fixture of the contract tests, never run")
