"""Bytes the transfer engine moved (host to device, device to host, device to
device) per iteration, from the runtime's own counters: a count, the same in
every run of a cell."""


def read(ctx):
    c = ctx["counters"]
    if not ctx["iterations"] or "bytes_h2d" not in c:
        return None
    return (c["bytes_h2d"] + c["bytes_d2h"] + c["bytes_d2d"]) / \
        ctx["iterations"]
