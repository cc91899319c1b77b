"""Operations and bytes computed from shapes, kept with the benchmark so
that no later change to the program can alter how its work is counted.

``macs_per_clip`` is the 3-D ResNet multiply-accumulate count of
``models/resnet3d.macs_per_clip`` (Hara et al. BasicBlock ResNets, stem
3x7x7 at stride 2, one stride-2 stage entry per later stage, 1x1x1
projection shortcuts where the width changes; GroupNorm, ReLU, pooling and
the classifier are not counted). A training step on one clip costs the
forward pass and a backward pass of twice its operations: 6·MAC FLOPs.
"""
from __future__ import annotations

STAGE_WIDTHS = (1, 2, 4, 8)          # multiples of the stem width


def macs_per_clip(blocks, stem_width: int, frames: int, size: int) -> float:
    """Multiply-accumulates of one forward pass over one clip."""
    w0 = stem_width
    t, hw = frames / 2, size / 2            # stem stride 2
    macs = (t * hw * hw) * 3 * 7 * 7 * 3 * w0
    c_in = w0
    for si, nblk in enumerate(blocks):
        c_out = w0 * STAGE_WIDTHS[si]
        if si > 0:
            t, hw = max(t / 2, 1), hw / 2
        vox = t * hw * hw
        for bi in range(nblk):
            cin = c_in if bi == 0 else c_out
            macs += vox * 27 * (cin * c_out + c_out * c_out)
            if cin != c_out:
                macs += vox * cin * c_out
        c_in = c_out
    return float(macs)


def model_macs(cfg: dict, model: str) -> float:
    """MACs per clip of ``cfg["models"][model]`` at the config's clip."""
    frames, size = cfg["clip"][0], cfg["clip"][1]
    return macs_per_clip(cfg["models"][model]["blocks"], cfg["stem_width"],
                         frames, size)


def kd_step_flops_per_clip(cfg: dict) -> float:
    """One KD clip: the teacher's forward (2·MAC) and the student's forward
    and backward (6·MAC)."""
    return 2 * model_macs(cfg, "teacher") + 6 * model_macs(cfg, "student")


def train_flops_per_clip(cfg: dict) -> float:
    """One fine-tune clip: the student's forward and backward."""
    return 6 * model_macs(cfg, "student")


# elementwise operations per logit in the fused KD loss forward: running
# max, subtract, exp, sum, label compare-select-sum, (s - t), scale by
# 1/T, square, sum
KD_LOSS_OPS_PER_LOGIT = 9


def kd_loss_forward_cost(rows: int, classes: int, row_block: int = 8,
                         vocab_block: int = 512):
    """(FLOPs, bytes) of one forward call of the fused KD loss on (rows,
    classes) float32 logits: both logit tensors read once, the per-row
    labels and mask read and the per-row loss written, at the kernel's
    padded block shapes (rows to a multiple of 8, classes to whole
    vocabulary tiles)."""
    rp = -(-rows // row_block) * row_block
    vb = min(vocab_block, classes)
    vp = -(-classes // vb) * vb
    flops = KD_LOSS_OPS_PER_LOGIT * rp * vp
    nbytes = 2 * rp * vp * 4 + 3 * rp * 4
    return float(flops), float(nbytes)
