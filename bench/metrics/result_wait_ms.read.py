"""Mean milliseconds a read's ``result()`` waited for its batch to be
resolved (engine/queue.py ``QueueFuture.result``, span ``queue.wait``,
demand flushes included), on the client thread: growth of the sum of
``queue_result_wait_seconds{path="probe"}`` over growth of its count,
over the window less its traced part."""
from bench.record import hist_mean_cases

HIST = "queue_result_wait_seconds"


def read(run):
    return run.hist_mean_ms(HIST, path="probe")


def cases():
    return hist_mean_cases(HIST, path="probe")
