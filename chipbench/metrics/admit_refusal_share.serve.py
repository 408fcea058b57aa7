"""Admissions the KV pool refused (``sched_stats["admission_refusals"]``,
one per step at most) per engine step of the window, in %."""


def read(data):
    steps = data.get("steps")
    if not steps:
        return None
    return 100 * sum(s["refusals"] for s in steps) / len(steps)
