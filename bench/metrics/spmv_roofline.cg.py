"""Per cent of the memory roofline of one CSRC product: the least time
its bytes (ad, ia, ja, al, au, x, y) take at the chip's HBM peak, over the
device time of one ``op(x)`` of the operator ``cg_solve`` returned,
measured in a traced burst after the window."""
from cost import roofline_share
from peaks import peaks_for


def read(ctx):
    b = getattr(ctx, "bursts", {}).get("spmv")
    if not b or not b["device_s"]:
        return None
    return roofline_share(b["bytes"], b["device_s"],
                          peaks_for(ctx.device_kind)["hbm_bytes_per_s"])
