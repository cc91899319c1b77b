"""step_mfu.<kind>: model FLOPs of the clips completed in the traced
window (``flops.py``: KD 2·MAC_teacher + 6·MAC_student per clip, fine-tune
6·MAC per unmasked clip) over window × chips × the chip's bf16 peak
(``peaks.json``)."""


def read(name, m):
    if not m["flops"] or not m["window_s"]:
        return None
    return 100.0 * m["flops"] / (m["window_s"] * m["chips"]
                                 * m["peaks"]["flops_bf16"])
