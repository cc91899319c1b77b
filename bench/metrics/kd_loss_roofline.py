"""kd_loss_roofline: the fused KD loss kernel's share of its roofline.

The kernel's events are the ``custom-call`` ops on the (rows, classes)
float32 logits, padded to its blocks, in the traced window. The least time
a call could take is max(FLOPs / bf16 peak, bytes / HBM bandwidth) from
``flops.kd_loss_forward_cost``; at these shapes the bytes bound it. Only
the forward kernel is counted: its analytic backward is plain XLA fusions
that the trace does not name apart from the rest of the step.
"""
import flops


def read(name, m):
    t = m["trace"]
    if t is None or not m.get("kd_rows"):
        return None
    rows, classes = m["kd_rows"], m["classes"]
    f, b = flops.kd_loss_forward_cost(rows, classes)
    rp = -(-rows // 8) * 8
    shape = f"f32[{rp},{classes}]"

    def match(op):
        return "custom-call(" in op and shape in op.split("custom-call(")[1]

    calls, secs = t.op_count(match), t.op_time_s(match)
    if not calls or secs <= 0:
        return None
    least = max(f / m["peaks"]["flops_bf16"], b / m["peaks"]["hbm_bytes_per_s"])
    return 100.0 * calls * least / secs
