"""As device_idle.read, in the read-write cell (ycsb_a.zipf)."""


def read(run):
    return run.device_idle()
