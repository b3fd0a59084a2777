"""Mean milliseconds a flush spent staging its one dispatch, the
deployment's probe function (``Index.lookup``), under the queue lock
(engine/queue.py ``_flush_locked``, span ``queue.dispatch``): growth of
the sum of ``dispatch_staging_seconds{path="probe"}`` over growth of its
count, over the window less its traced part."""
from bench.record import hist_mean_cases

HIST = "dispatch_staging_seconds"


def read(run):
    return run.hist_mean_ms(HIST, path="probe")


def cases():
    return hist_mean_cases(HIST, path="probe")
