"""Share of the window's wall time spent in admission passes
(``run_admission``: planning, prefill, the admission burst), in %."""


def read(run):
    w = run.window
    busy = sum(min(b, w.seconds) - a for a, b in w.admissions
               if a < w.seconds)
    return 100.0 * busy / w.seconds
