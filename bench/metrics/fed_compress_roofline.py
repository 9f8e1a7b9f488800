"""Share of its roofline that the fed_compress kernel (the custom call
``%fed.upload_transform.N``, one a round) reaches in the traced window.
The kernel's HBM bytes come from the call's shapes
(``flops/fed_compress.py``: K rows of the upload's P params); it does no
matrix-unit work, so the roofline is the bandwidth bound: bytes over the
chip's HBM bandwidth, over the kernel's device time."""


def read(ctx):
    seconds, calls = ctx.trace.kernel("fed.upload_transform")
    if not calls or seconds <= 0:
        return None
    K = int(ctx.cell.traffic["server"]["n_selected"])
    ops, nbytes = ctx.flops("fed_compress").cost(K, ctx.window["n_params"])
    bound_s = max(ops / ctx.peaks["bf16_flops"],
                  nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * calls * bound_s / seconds
