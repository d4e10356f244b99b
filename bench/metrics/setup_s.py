"""Set-up seconds: from process start to the window opening (imports,
weights, engine, warm-up passes and every compile or cache load in them)."""


def read(run):
    return run.setup_s
