"""The privacy chain's share of its HBM roofline: the bytes it cannot
avoid in a round (``flops.chain_bytes``: read the clients' f32 updates
once, write the limb states) at the chip's HBM bandwidth, over the
chain's device time per round. HBM is the only bound stated: the KDF
mask expansion is ALU work with no published peak."""


def read(ctx):
    ns = ctx["program_ns"]("chain")
    if not ns or not ctx["rounds"]:
        return None
    least_s = ctx["chain_bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ns / 1e9 / ctx["rounds"])
