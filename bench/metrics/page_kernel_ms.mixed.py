"""As page_kernel_ms.read, in the read-write cell (ycsb_a.zipf)."""


def read(run):
    return run.page_kernel_ms()
