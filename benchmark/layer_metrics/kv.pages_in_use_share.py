"""Share of the page pool's allocatable pages that were ever in use at once,
in percent: the pool's high-water mark over its capacity (the trash page is
not allocatable).  Memory reserved against memory in use."""


def read(run):
    high, pages = run.facts.get("pool_high_water"), run.facts.get("pool_pages")
    if high is None or not pages:
        return None
    return 100.0 * high / pages
