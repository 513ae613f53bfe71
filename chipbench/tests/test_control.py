"""The control fails where the program passes, at a size a test run can
hold: the fp8 reference in the program's place, and the reference fed
half of each batch, read further from the float32 reference than the
program does, on one of the compared numbers at least; and the fp8
reference, put in the program's place in a whole run of the harness,
comes out not correct against the cell's own limits."""

import shutil

from chipbench import calibrate, run


def test_control_and_half_batch_read_far_above_the_program(tiny_root):
    prepared = calibrate.prepare("tiny.binned", root=tiny_root,
                                 require_tpu=False)
    try:
        lines = [calibrate.readings(prepared, seed, control=True)
                 for seed in (21, 22)]
    finally:
        shutil.rmtree(prepared[2], ignore_errors=True)
    s = calibrate.summary(lines)
    prog = s["program_max"]
    for kind in ("control_fp8_min", "fault_half_batch_min"):
        ratios = {k: s[kind][k] / max(prog[k], 1e-12) for k in prog}
        assert max(ratios.values()) >= 3.0, (kind, prog, s[kind])
    cell = prepared[0]
    for line in lines:
        assert run.judge(cell, line["program"])[0], line
        assert not run.judge(cell, line["control_fp8"])[0], line
        assert not run.judge(cell, line["fault_half_batch"])[0], line


def test_fp8_control_run_is_not_correct(tiny_root):
    result, compared = run.run("tiny.binned", 12, 2, 0,
                               root=tiny_root, require_tpu=False,
                               control="fp8")
    assert not result["correct"], compared
    assert result["failed"] == 0
    assert compared["loader_rows_not_in_shards"][0] == 0


def test_limits_lie_between_the_readings():
    s = {"program_max": {"loss_rel_gap": 2e-5, "grad_norm_rel_gap": 1e-3,
                         "grad1_leaf_gap": 0.01, "change_leaf_gap": 0.004},
         "control_fp8_min": {"loss_rel_gap": 8e-5, "grad_norm_rel_gap": 2e-3,
                             "grad1_leaf_gap": 0.006,
                             "change_leaf_gap": 0.007},
         "fault_half_batch_min": {"loss_rel_gap": 2e-3,
                                  "grad_norm_rel_gap": 0.4,
                                  "grad1_leaf_gap": 0.4,
                                  "change_leaf_gap": 0.2},
         "seeds": [1, 2], "control_seeds": [1]}
    spec = calibrate.set_limits(s, "TPU v5 lite")
    upper = spec["readings"]["upper"]
    assert upper["loss_rel_gap"][1].startswith("fp8 control")
    assert upper["grad_norm_rel_gap"][1].startswith("half-batch fault")
    assert upper["grad1_leaf_gap"][0] == 0.4
    assert upper["change_leaf_gap"][1].startswith("half-batch fault")
    for k, low in s["program_max"].items():
        lim = spec["limits"][k]
        assert low < lim < upper[k][0], (k, low, lim, upper[k])
        # More room above the lower than below the upper, by ratio.
        assert lim / low > upper[k][0] / lim
    assert spec["limits"]["loader_rows_not_in_shards"] == 0
