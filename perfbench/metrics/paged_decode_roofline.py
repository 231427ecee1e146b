"""Paged decode attention kernel: least time for the decode attention the
window's tokens needed (their real context lengths) over the kernel's
device time in the trace, %."""


def read(run):
    _, work = run.decode_work()
    return run.roofline(r"^paged_decode_attention", work)
