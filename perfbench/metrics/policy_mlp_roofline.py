"""Deep-scorer kernel (``policy_mlp``): share of its roofline.  The least
time is computed from the unpadded rows the prioritizer handed the scorer
in the traced window and the actor's layer widths, so the count does not
depend on how the scorer pads or blocks them; the time is the kernel's
device time in the trace."""
import roofline


def read(ctx):
    red, tail = ctx["trace_result"], ctx["tail"]
    if red is None or tail is None or tail.tail_rows == 0:
        return None
    kernel_s = red["kernel_s"].get("policy_mlp", 0.0)
    if kernel_s <= 0.0:
        return None
    widths = ctx["config"]["scheduler"]["actor"]["widths"]
    least = roofline.roofline_s(
        roofline.mlp_flops(tail.tail_rows, widths),
        roofline.mlp_bytes(tail.tail_rows, widths, calls=tail.tail_calls),
        ctx["device_kind"])
    return 100.0 * least / kernel_s
