"""Whole model step: FLOPs of every token computed in the window (uncached
prefill and decode, attention over real lengths) over window x bf16
peak, %."""


def read(run):
    return run.mfu()
