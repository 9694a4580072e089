"""Bytes the transfer engine copied from one chip to another per iteration,
from the runtime's ``bytes_d2d`` counter: halo faces whose neighbour chunk
lives on another chip, and any chunk that moves between chips. A count."""


def read(ctx):
    c = ctx["counters"]
    if not ctx["iterations"] or "bytes_d2d" not in c:
        return None
    return c["bytes_d2d"] / ctx["iterations"]
