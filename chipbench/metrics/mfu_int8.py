"""The served step's share of the chip's int8 peak, in %: the network's
int8 operations (2 x conv, dense and depthwise MACs) times the images
completed in the window, over the window's seconds times the peak."""
from chipbench import work


def read(rec):
    images = len(rec["counted"])
    if images == 0 or rec["window_s"] <= 0:
        return None
    ops = work.int8_ops_per_image(rec["config"]) * images
    return 100.0 * ops / rec["window_s"] / rec["peaks"]["int8_ops_per_s"]
