"""As queue_batch.read, in the read-write cell (ycsb_a.zipf)."""


def read(run):
    return run.queue_batch()
