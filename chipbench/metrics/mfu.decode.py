"""mfu.decode: the decode step's share of the chip's roofline over the
untraced window: the least time each step could take, max(required FLOPs /
peak bf16, required bytes / HBM bandwidth) from ``count.decode_step_work``,
summed over the steps that the window's token gaps hold (one batch's
``decode_work`` per batch), over the gaps' measured time."""


def read(ctx):
    pk = ctx["peaks"]
    if pk is None:
        return None
    w = ctx["window"]
    least = sum(max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
                for f, b in ctx["decode_work"])
    return least * len(w["batches"]) / sum(w["gaps"]) * 100.0
