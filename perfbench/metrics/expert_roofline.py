"""The held experts' grouped products' share of their roofline, in
percent: the least time of a traced step's grouped products (the
configuration module's ``expert_passes``: per MoE layer and weight the
forward, data-gradient and weight-gradient pass over the rows routed to
the held experts, each the larger of its FLOPs over the peak and its
bytes over the bandwidth), over the device time per step of the
operations under the model's ``moe/experts`` scope (``model_scopes``;
recompute included)."""
from perfbench import model_scopes


def read(ctx):
    passes = getattr(ctx.cell.config_module, "expert_passes", None)
    spent = model_scopes.scope_ms(ctx, "moe", "experts")
    if passes is None or spent is None:
        return None
    pk = ctx.peaks
    bounds = [(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
              for f, b in passes(ctx.cell.config, ctx.cell.traffic,
                                 ctx.raw.global_batch)]
    least = sum(max(t) for t in bounds)
    ctx.say(f"expert roofline: least {1e3 * least:.6f} ms per step "
            f"({sum(f >= b for f, b in bounds)} of {len(bounds)} passes "
            f"bound by FLOPs), ops under moe/experts {spent:.6f} ms")
    return 100 * 1e3 * least / spent
