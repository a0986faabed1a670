"""Drives a designer's compute-IR program by hand, the way one flush of the
batch executor does: resolve → prepare each → ONE device_program → finalize
each. The one copy of that sequence for the tests that hold a fused flush
against the sequential ``suggest`` without an executor's threads."""

from vizier_tpu.compute import registry as compute_registry


def bucket_key(designer, count=1):
    """The designer's bucket for a ``count``-suggest; None = sequential."""
    resolved = compute_registry.resolve(designer, count)
    return None if resolved is None else resolved[1]


def flush(designers, count=1, pad_to=None):
    """One fused flush of same-bucket ``designers``; a suggestion list each.

    Slot 0's program runs the device body, as in the executor."""
    resolved = [compute_registry.resolve(d, count) for d in designers]
    assert all(r is not None for r in resolved), "an unbatchable designer"
    keys = [key for _, key in resolved]
    assert keys.count(keys[0]) == len(keys), f"buckets differ: {keys}"
    programs = [program for program, _ in resolved]
    items = [p.prepare(d, count) for p, d in zip(programs, designers)]
    outputs = programs[0].device_program(items, pad_to=pad_to)
    assert len(outputs) == len(designers)  # padding slots dropped at demux
    return [
        list(p.finalize(d, item, output))
        for p, d, item, output in zip(programs, designers, items, outputs)
    ]


def pass_prepare(chaotic):
    """Steps a ChaosDesigner's programs over the prepare strike, so that a
    schedule reaches ``device_program`` (and the sequential ``suggest``)."""
    resolve = chaotic.compute_program

    def compute_program(count=None):
        resolved = resolve(count)
        if resolved is not None:
            program = resolved[0]
            program.prepare = lambda designer, count: program._inner.prepare(
                designer._inner, count
            )
        return resolved

    chaotic.compute_program = compute_program
