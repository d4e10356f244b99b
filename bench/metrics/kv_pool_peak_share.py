"""Most KV pages in use after any decode step, over the pool's pages, in %
(read from the allocator's state after every step: traced runs only)."""


def read(run):
    if not run.kv_peak_used:
        return None
    return 100.0 * run.kv_peak_used / run.num_pages
