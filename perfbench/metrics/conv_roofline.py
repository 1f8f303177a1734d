"""The convolutions' share of their roofline, in percent: the least time
of every traced step's convolution passes on one chip (``counts``: per
pass the larger of FLOPs over peak and bytes over bandwidth, for the
ResNet-50 of the cell's configuration), over the device time of the
trace's convolution operations, averaged over the chips."""
from perfbench import counts, trace


def read(ctx):
    if ctx.trace is None or ctx.steps == 0:
        return None
    per_chip = [trace.kind_s(ops, ctx.trace.window, trace.is_convolution)
                for ops in ctx.trace.devices.values()]
    spent = sum(per_chip) / len(per_chip)
    if spent <= 0:
        return None
    c = ctx.cell.config
    least = counts.conv_least_time(
        ctx.cell.traffic["batch_per_chip"], ctx.peaks["bf16_flops_per_s"],
        ctx.peaks["hbm_bytes_per_s"], width=c["width"],
        image=c["image_size"], n_classes=c["n_classes"])
    ctx.say(f"conv roofline: least {least.seconds:.6f} s per step "
            f"({least.compute_bound} passes bound by FLOPs, "
            f"{least.memory_bound} by bytes; FLOPs bound {least.flops_seconds:.6f} s, "
            f"bytes bound {least.bytes_seconds:.6f} s), convolution ops "
            f"{spent / ctx.steps:.6f} s per step")
    return 100 * least.seconds * ctx.steps / spent
