"""Device milliseconds a lookup dispatch costs: device time of every
program launched inside the harness span around the queue's probe call
(the fused lookup and whatever it launches besides), over the number of
such calls, in the traced part of the window."""


def read(run):
    return run.per_dispatch_ms(run.lookup_device_s())
