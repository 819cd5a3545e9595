"""Tokens per step / median step period / chips (host clock, one
``block_until_ready`` on the loss per step)."""
from benchmarks import training


def read(r):
    return training.tokens_per_s_per_chip(r) if r["kind"] == "training" else None
