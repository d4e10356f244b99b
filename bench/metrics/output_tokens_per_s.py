"""Output tokens that reached the host inside the window, over the
window's seconds: every request's tokens, also those of requests still
running at the close."""


def read(run):
    w = run.window
    n = sum(1 for r in w.records.values() for t in r.times if t <= w.seconds)
    return n / w.seconds
