"""collective_exposed_ms.train: time per run of the compiled train step in
the traced window in which a collective (all-gather, reduce-scatter,
all-reduce, ...) runs on a device and no other operation does, averaged
over the chips; nothing where no collective ran."""
from chipbench.trace import module_stats


def read(ctx):
    red = ctx["trace"]
    runs, _, _ = module_stats(red, r"train_step")
    if not runs or not red["collective_s"]:
        return None
    return red["collective_exposed_s"] / runs * 1e3
