"""Per cent of the memory roofline of one sharded CSRC product: one
chip's least bytes (``cost_mesh.mesh_spmv_shard_bytes``: its share of the
product and its halo rows of x in and y out) at the chip's HBM peak, over
the first chip's device time of one product of the operator
``cg_solve(mesh_p=...)`` returned, its collectives included, measured in
a traced burst after the window."""
from cost import roofline_share
from peaks import peaks_for


def read(ctx):
    b = getattr(ctx, "bursts", {}).get("spmv")
    if not b or not b["device_s"]:
        return None
    return roofline_share(b["bytes"], b["device_s"],
                          peaks_for(ctx.device_kind)["hbm_bytes_per_s"])
