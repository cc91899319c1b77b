"""The benchmark's operation counts: equal to the program's own MAC count
at the published widths, and the KD loss kernel's cost from its shapes."""
import flops


def test_macs_match_published_widths():
    r18 = flops.macs_per_clip((2, 2, 2, 2), 64, 8, 112)
    r34 = flops.macs_per_clip((3, 4, 6, 3), 64, 8, 112)
    assert round(r18 / 1e9, 3) == 10.784
    assert round(r34 / 1e9, 3) == 19.801


def test_macs_match_program():
    from repro.configs import get_config
    from repro.models.resnet3d import macs_per_clip
    for name, blocks in (("resnet3d-18", (2, 2, 2, 2)),
                         ("resnet3d-34", (3, 4, 6, 3))):
        assert flops.macs_per_clip(blocks, 64, 8, 112) == \
            macs_per_clip(get_config(name))


def test_kd_step_flops():
    cfg = {"models": {"teacher": {"blocks": [3, 4, 6, 3]},
                      "student": {"blocks": [2, 2, 2, 2]}},
           "stem_width": 64, "clip": [8, 112, 112, 3]}
    per_clip = flops.kd_step_flops_per_clip(cfg)
    assert abs(per_clip - (2 * 19.80145664e9 + 6 * 10.784227328e9)) < 1
    assert flops.train_flops_per_clip(cfg) == 6 * 10.784227328e9


def test_kd_loss_cost_from_shapes():
    f, b = flops.kd_loss_forward_cost(64, 400)
    assert f == 9 * 64 * 400
    assert b == 2 * 64 * 400 * 4 + 3 * 64 * 4
    # rows pad to 8, classes to whole 512-wide tiles
    f2, b2 = flops.kd_loss_forward_cost(3, 1000)
    assert f2 == 9 * 8 * 1024
    assert b2 == 2 * 8 * 1024 * 4 + 3 * 8 * 4
    # bytes bound it on a v5e: 9 flop per 8 bytes is far below the ridge
    assert b2 / 819e9 > f2 / 197e12
