"""Model FLOPs of the co-located rounds (decode for the active slots plus
the finetune units each ran) over the device time of their programs times
the chip's bf16 peak, in %: the whole round's share of the peak."""

from harness.readers import round_mfu


def read(w):
    return round_mfu(w)
