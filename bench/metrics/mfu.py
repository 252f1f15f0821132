"""The whole model step's share of the chip's bf16 peak in the traced
segment, in %: model FLOPs really computed there — 2 x parameters per
token prefilled or decoded in an active lane, plus attention (causal
prefill 2*H*hd*T^2 per layer; decode 4*H*hd*context per layer and
token; H*hd summed over layers is the architecture's ``attn_width``) —
over the traced segment's length times the peak."""


def read(ctx):
    a, b = ctx["trace_wall"]
    p = ctx["params"]          # the architecture's ``param_count``
    hh = ctx["attn_width"]     # and its ``attn_width``
    flops = 0.0
    for t0, _, n in ctx["hooks"].prefills:
        if a <= t0 <= b:
            flops += 2.0 * p * n + 2.0 * hh * n * n
    for t0, _, active, pos in ctx["hooks"].ticks:
        if a <= t0 <= b:
            flops += 2.0 * p * active + 4.0 * hh * pos
    if flops <= 0:
        return None
    return 100.0 * flops / (ctx["traced_s"] * ctx["peaks"]["bf16_flops"])
