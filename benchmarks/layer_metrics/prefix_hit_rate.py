"""Prompt tokens served from the radix cache over prompt tokens, across the
completed requests due in the window (each reply's ``usage.cached_tokens``)."""


def read(r):
    done = [s for s in r.get("in_window", []) if s.done is not None and s.error is None]
    prompt = sum(s.prompt_tokens for s in done)
    return 100.0 * sum(s.cached_tokens for s in done) / prompt if prompt else None
