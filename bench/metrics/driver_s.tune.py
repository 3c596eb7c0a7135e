"""Seconds per job of the search driver's own work: the self time of
``driver.propose``, ``driver.acquire`` and ``driver.observe``."""
from harness.spans import self_s


def read(ctx):
    jobs = ctx.window.records
    if not jobs or not ctx.events:
        return None
    return self_s(ctx.events, {"driver.propose", "driver.acquire",
                               "driver.observe"}) / len(jobs)
