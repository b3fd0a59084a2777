"""Mean milliseconds a ``submit`` waited to take the queue lock
(engine/queue.py ``_TimedLock``, inside span ``queue.submit``), on the
sender: a flush stages its dispatch under the lock. Growth of the sum of
``queue_lock_wait_seconds{path="probe", op="submit"}`` over growth of its
count, over the window less its traced part."""
from bench.record import hist_mean_cases

HIST = "queue_lock_wait_seconds"


def read(run):
    return run.hist_mean_ms(HIST, path="probe", op="submit")


def cases():
    return hist_mean_cases(HIST, path="probe", op="submit")
