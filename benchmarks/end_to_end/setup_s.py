"""Process start to the first measured request or step: weights, the
correctness check, prewarm or warm-up steps, compilation where the cache
misses."""


def read(r):
    return r["setup_s"]
