"""Mean milliseconds a deadline flush ran past the queue's effective
deadline (engine/queue.py ``_deadline_flush``), on the timer thread:
growth of the sum of ``queue_deadline_late_seconds{path="probe"}`` over
growth of its count, over the window less its traced part."""
from bench.record import hist_mean_cases

HIST = "queue_deadline_late_seconds"


def read(run):
    return run.hist_mean_ms(HIST, path="probe")


def cases():
    return hist_mean_cases(HIST, path="probe")
