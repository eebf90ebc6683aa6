"""Per cent of the memory roofline of one assembly: the least time its
bytes (element matrices and connectivity in, CSRC values out) take at the
chip's HBM peak, over the device time of one ``assemble`` call, measured
in a traced burst after the window."""
from cost import roofline_share
from peaks import peaks_for


def read(ctx):
    b = getattr(ctx, "bursts", {}).get("assemble")
    if not b or not b["device_s"]:
        return None
    return roofline_share(b["bytes"], b["device_s"],
                          peaks_for(ctx.device_kind)["hbm_bytes_per_s"])
