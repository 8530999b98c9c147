"""(q tile, kv tile) grid steps the attention kernels built during set-up
visit, over the steps the rectangle of their padded bounds would walk, in
%: the lowering counters ``band_tiles`` / ``rect_tiles`` when the window
starts.  A program without the counters has nothing to read."""


def read(ctx):
    band = ctx.counters_before.get("lowering.band_tiles")
    rect = ctx.counters_before.get("lowering.rect_tiles")
    if band is None or not rect:
        return None
    return 100.0 * band / rect
