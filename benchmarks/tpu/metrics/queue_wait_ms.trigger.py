"""Scheduler and packing: mean of the engine's ``StreamStats.queue_wait_s``
(arrival in the engine to the start of its batch's build) over the
window's requests."""


def read(run):
    if not run.queue_wait_s:
        return None
    return sum(run.queue_wait_s) / len(run.queue_wait_s) * 1e3
