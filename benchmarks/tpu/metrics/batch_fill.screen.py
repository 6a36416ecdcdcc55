"""Scheduler and packing: graphs per dispatched batch over the queue's
``max_batch``, over the window's batches (``StreamStats.batch_sizes``)."""


def read(run):
    if not run.batch_sizes:
        return None
    return 100.0 * sum(run.batch_sizes) / (len(run.batch_sizes)
                                           * run.max_batch)
