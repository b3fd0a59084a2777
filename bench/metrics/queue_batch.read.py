"""Queries a flush of the micro-batch queue (engine/queue.py) over the
window, from QueueStats: how deep the batches the reads ride in are."""


def read(run):
    return run.queue_batch()
