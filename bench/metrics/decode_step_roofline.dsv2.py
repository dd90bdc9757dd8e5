"""Share of its roofline a decode step reaches: the needed bytes and
FLOPs of this chip's share (mla_moe family: held experts read where
routed to, the latent cache) over the peaks, over the device time of
one step."""

import readers


def read(rec):
    return readers.decode_roofline(rec)
