"""Share of its roofline a decode step reaches: the needed bytes and
FLOPs over the peaks, over the device time of one step."""

import readers


def read(rec):
    return readers.decode_roofline(rec)
