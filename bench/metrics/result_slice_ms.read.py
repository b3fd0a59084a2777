"""Mean milliseconds a read's ``result()`` spent slicing its rows out of
the shared batch result (engine/queue.py ``QueueFuture.result``, span
``queue.slice``), on the client thread: growth of the sum of
``queue_slice_seconds{path="probe"}`` over growth of its count, over the
window less its traced part."""
from bench.record import hist_mean_cases

HIST = "queue_slice_seconds"


def read(run):
    return run.hist_mean_ms(HIST, path="probe")


def cases():
    return hist_mean_cases(HIST, path="probe")
