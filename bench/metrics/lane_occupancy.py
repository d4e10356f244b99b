"""Mean lanes that made a token per decode step, over the lanes, in %."""


def read(run):
    steps = run.window.steps
    if not steps:
        return None
    return 100.0 * sum(s.active for s in steps) / (len(steps) * run.lanes)
