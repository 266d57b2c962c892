"""mfu.train: required model FLOPs per second of the untraced window
(``count.train_flops_per_step`` x steps / window) over chips x peak bf16."""


def read(ctx):
    if ctx["peaks"] is None:
        return None
    w = ctx["window"]
    rate = ctx["flops_per_step"] * w["steps"] / w["seconds"]
    return rate / (ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]) * 100.0
