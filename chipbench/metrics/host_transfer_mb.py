"""MB (10^6 bytes) per round moved between host and device at the sites
the program counts: the ``h2d_bytes`` and ``d2h_bytes`` of its spans
(snapshot, batches and host parameters, waves pulled to the host, rows
raveled from the host), summed over the traced rounds."""


def read(ctx):
    n = ctx.get("transfer_bytes")
    if n is None or not ctx["rounds"]:
        return None
    return n / 1e6 / ctx["rounds"]
