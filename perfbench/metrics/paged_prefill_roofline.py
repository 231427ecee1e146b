"""Paged chunk-prefill attention kernel: least time for the prefill
attention of the window's prompts (uncached tokens over their real
context, however chunked) over the kernel's device time in the trace, %."""


def read(run):
    _, work = run.prefill_work()
    return run.roofline(r"^paged_prefill_attention", work)
