"""What the readers of the step clock's host side share (no metric of its
own): the window's records of a program whose clock names every part of
``host_ms``.  Such a program's records carry ``put_ms``; one from before
(PR 37 and earlier) has ``pack_ms`` and ``commit_ms`` of another meaning
(packing with the puts and the launch; a commit that ends at its record),
so every reader here returns None for it, and not that program's numbers
under this one's names."""


def records(run) -> list:
    return [s for s in run.steps if getattr(s, "put_ms", None) is not None]


def part_mean(run, part: str):
    """Mean of ``StepRecord.<part>_ms`` over the window's records."""
    steps = records(run)
    if not steps:
        return None
    return sum(getattr(s, part + "_ms") for s in steps) / len(steps)


def share(run, numerator: str, *denominator: str):
    """Sum of one field over the sum of others, over the window."""
    steps = records(run)
    below = sum(getattr(s, name) for s in steps for name in denominator)
    if not below:
        return None
    return sum(getattr(s, numerator) for s in steps) / below
