"""Share of the KIVI Pallas kernels' roofline in the traced segment, in %:
the least time the chip could take for every quantize and dequantize
call made in the segment (bytes over HBM bandwidth or operations over
peak, the larger, from ``kivi_cost``) over the kernels' device time in
the trace. The KIVI kernels are the only Pallas kernels on the served
path, so their device events are those of ``tpu_custom_call`` or of the
kernels' own names. Nothing is returned when the segment ran none."""
from bench import kivi_cost, trace

KERNELS = ("_quant_pack_kernel", "_dequant_kernel", "tpu_custom_call")


def read(ctx):
    dev_ns = trace.kernel_ns(ctx["device_events"], KERNELS)
    a, b = ctx["trace_wall"]
    calls = [c for c in ctx["hooks"].kivi if a <= c[0] <= b]
    if dev_ns <= 0 or not calls:
        return None
    pk = ctx["peaks"]
    least = 0.0
    for _, kind, rows, cols, bits, group in calls:
        cost = (kivi_cost.quantize_cost if kind == "q"
                else kivi_cost.dequantize_cost)
        nbytes, ops = cost(rows, cols, bits, group)
        least += kivi_cost.least_time_s(nbytes, ops, pk["bf16_flops"],
                                        pk["hbm_bps"])
    return 100.0 * least / (dev_ns * 1e-9)
