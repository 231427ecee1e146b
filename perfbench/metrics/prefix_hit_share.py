"""KV pool and prefix cache: cached prompt tokens over prompt tokens of the
requests due in the window, from their Usage counts, %."""


def read(run):
    rs = [r for r in run.due_in_window() if r.prompt_tokens]
    total = sum(r.prompt_tokens for r in rs)
    return 100.0 * sum(r.cached_tokens for r in rs) / total if total else None
