"""Prompt + output tokens completed per second of window, each request
credited by the share of its lifetime inside the window."""
from benchmarks import serving


def read(r):
    if r["kind"] != "serving":
        return None
    return serving.tokens_per_s(r["samples"], r["window"])
