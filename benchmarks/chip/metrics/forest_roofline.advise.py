"""forest_roofline.advise: the grouped forest kernel's least time over its
device time in the traced window.

Per launch the least time is the larger of its operations over the peak
bf16 rate and its bytes over the peak bandwidth (``roofline.least_time``).
The work is the algorithm's, not the tiling's: bytes are the unpadded node
tables of the groups the launch touches, the rows' features and their
per-tree leaf values (``roofline.forest_launch_bytes``); operations are one
compare per tree per level of each row's group depth. The kernel's device
time is the summed duration of its ``forest_grouped`` operations. A kernel
that ran with no launch recorded fails the run instead of going silent."""
from chipbench import roofline, tracereduce

KERNEL = "forest_grouped"


def read(ctx):
    if ctx.trace is None:
        return None
    device_s = tracereduce.op_seconds(
        ctx.extract["device"][sorted(ctx.extract["device"])[0]],
        tracereduce.window(ctx.extract), KERNEL)
    if device_s <= 0:
        return None
    if not ctx.forest_launches:
        raise RuntimeError(
            f"{KERNEL} ran {device_s:.6f} s on the device in the window, "
            "but no launch was recorded: the kernel is reached through a "
            "call that chipbench/spans.py does not wrap")
    nodes = {pair: n for pair, n in zip(ctx.ref_pairs, ctx.forest_nodes)}
    least, bounds = 0.0, {}
    for _, _, rows, gids, ops in ctx.forest_launches:
        nbytes = roofline.forest_launch_bytes(
            [nodes[ctx.bank_pairs[g]] for g in gids], rows,
            ctx.n_features, ctx.cfg["n_trees"])
        t, bound = roofline.least_time(ops, nbytes,
                                       ctx.peaks["bf16_flops"],
                                       ctx.peaks["hbm_bytes_per_s"])
        least += t
        bounds[bound] = bounds.get(bound, 0) + 1
    ctx.notes.append(f"forest_roofline.advise: {len(ctx.forest_launches)} "
                     f"launches, bound by {bounds}, least {least:.6f} s "
                     f"of {device_s:.6f} s device time")
    return 100.0 * least / device_s
