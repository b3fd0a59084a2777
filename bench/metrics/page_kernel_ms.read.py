"""Device milliseconds a lookup dispatch spends in the page kernel
(kernels/page_search.py, under tiered/page_kernel): self time of the Pallas
kernels in the lookup programs other than the top descent, over the
number of lookup calls, in the traced part of the window."""


def read(run):
    return run.page_kernel_ms()
