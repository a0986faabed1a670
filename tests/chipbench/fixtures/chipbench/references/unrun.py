"""A fixture for the contract tests: the file a configuration's
``reference`` names. No run executes it."""


def compare(study_record, trained, config, rng):
    raise NotImplementedError("a fixture of the contract tests, never run")
