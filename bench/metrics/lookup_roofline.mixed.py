"""As lookup_roofline.read, in the read-write cell (ycsb_a.zipf)."""


def read(run):
    return run.lookup_roofline()
