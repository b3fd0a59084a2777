"""As lookup_device_ms.read, in the read-write cell (ycsb_a.zipf)."""


def read(run):
    return run.per_dispatch_ms(run.lookup_device_s())
