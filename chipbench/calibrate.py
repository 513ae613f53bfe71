"""Readings that the limits of a cell's comparison are set from.

For each seed, at the cell's own size and through the harness's own
set-up: the program's set-up steps against the float32 reference (the
program's reading), the fp8 control against the same reference (the
control's reading), and the reference fed half of each batch, the mean
taken over the rest, against the whole batch (the half-batch fault's
reading). A step that returns its state unchanged reads 1 on the worst
leaf's first gradient and change by definition and needs no run. The
corpus is the cell's own, so it is prepared once for all seeds. One JSON
line per seed, then a summary: the largest program reading and the
smallest control and fault readings of each number.

With ``--write-limits`` it then sets the cell's limits from those
readings and writes them, with the readings, to
``chipbench/cells/<workload>.json`` (see ``set_limits``), judges every
control and fault seed against them, and exits non-zero unless each
control and fault seed reads not correct.

    python3 chipbench/calibrate.py --workload <name> --seeds 1 2 3 ... \
        [--control-seeds 1 2 3] [--write-limits]
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from chipbench import cell as cells, check  # noqa: E402
from chipbench import run as harness  # noqa: E402


def prepare(workload, root=cells.BENCH_ROOT, require_tpu=True):
    """(cell, devices, work dir, balanced shard dir) for ``readings``."""
    import jax
    devices = jax.devices()
    cell = cells.load(workload, root)
    # Thread workers give the same batches in the same order as process
    # workers, without a pool to spawn for each seed.
    cell.traffic["loader"]["worker_mode"] = "thread"
    if require_tpu and devices[0].platform != "tpu":
        raise harness.NoChip("no TPU")
    harness.enable_cache()
    work = tempfile.mkdtemp(prefix="chipbench_cal_")
    bal = harness.prepare_data(cell, work)[0]
    return cell, devices, work, bal


def readings(prepared, seed, control):
    cell, devices, _, bal = prepared
    t = time.perf_counter()
    program = harness.Program(cell, seed, bal, devices)
    try:
        first, hosts = program.first_steps()[:2]
    finally:
        program.close()
    program_s = time.perf_counter() - t
    ref = harness.reference_readings(cell, seed, hosts)
    out = {"seed": seed, "widths": first["widths"], "program_s": program_s}
    kinds = [("program", first)]
    if control:
        kinds += [("control_fp8",
                   harness.reference_readings(cell, seed, hosts, "fp8")),
                  ("fault_half_batch",
                   harness.reference_readings(cell, seed, hosts, half=True))]
    for kind, reading in kinds:
        out[kind] = check.train_numbers(reading, ref)[0]
    out["seconds"] = time.perf_counter() - t
    return out


def summary(lines):
    def pick(kind, fn):
        rows = [ln[kind] for ln in lines if kind in ln]
        if not rows:
            return None
        return {k: fn(r[k] for r in rows) for k in rows[0]}
    return {"program_max": pick("program", max),
            "control_fp8_min": pick("control_fp8", min),
            "fault_half_batch_min": pick("fault_half_batch", min),
            "seeds": [ln["seed"] for ln in lines]}


# A number's upper reading is the least of these that reach the given
# multiple of its lower reading; a state left unchanged reads 1 on the
# first gradient and on the change by definition (no run).
UPPER_SOURCES = (("control_fp8_min", "fp8 control", 3.0),
                 ("fault_half_batch_min", "half-batch fault", 10.0),
                 ("state_unchanged", "state left unchanged (reads 1)", 3.0))
UNCHANGED_READS_ONE = ("grad1_leaf_gap", "change_leaf_gap")


def _sig3(x):
    return float("{:.3g}".format(x))


def set_limits(s, device_kind):
    """The limits file of a cell from a ``summary``: for each number,
    lower = the largest program reading, upper = the least of the
    ``UPPER_SOURCES`` readings that reach their multiple of the lower,
    limit = lower^(1/3) * upper^(2/3) (above the lower, below the upper,
    with more room above the lower). A number with no upper reading is
    not compared (it could only fail sound runs); it is listed with its
    readings. The loader counts are exact (0)."""
    lower = s["program_max"]
    limits, upper, not_compared = {}, {}, []
    for k, low in sorted(lower.items()):
        found = []
        for key, what, times in UPPER_SOURCES:
            if key == "state_unchanged":
                value = 1.0 if k in UNCHANGED_READS_ONE else None
            else:
                value = (s.get(key) or {}).get(k)
            if value is not None and value >= times * low:
                found.append((value, "{} ({:.3g}x the lower)".format(
                    what, value / low)))
        if not found:
            not_compared.append(k)
            continue
        upper[k] = [_sig3(v) if isinstance(v, float) else v
                    for v in min(found)]
        limits[k] = _sig3(low ** (1 / 3) * min(found)[0] ** (2 / 3))
    limits.update(loader_bad_rows=0, loader_rows_not_in_shards=0)
    return {
        "limits": limits,
        "rule": "limit = lower^(1/3) * upper^(2/3): above the lower "
                "reading, below the upper, with more room above the lower; "
                "the loader counts are exact (0)",
        "readings": {
            "source": "calibrate.py --write-limits on a {}, program seeds "
                      "{}, fp8 control and half-batch fault on {}".format(
                          device_kind, " ".join(map(str, s["seeds"])),
                          " ".join(map(str, s["control_seeds"]))),
            "lower": {k: _sig3(v) for k, v in sorted(lower.items())},
            "upper": upper,
            "control_fp8_min": {k: _sig3(v) for k, v in sorted(
                (s["control_fp8_min"] or {}).items())},
            "half_batch_min": {k: _sig3(v) for k, v in sorted(
                (s["fault_half_batch_min"] or {}).items())},
            "not_compared (no upper reading)": not_compared,
        },
    }


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--control-seeds", type=int, nargs="*", default=[])
    p.add_argument("--write-limits", action="store_true")
    args = p.parse_args(argv)
    prepared = prepare(args.workload)
    lines = []
    try:
        for seed in args.seeds:
            line = readings(prepared, seed, seed in args.control_seeds)
            print(json.dumps(line), flush=True)
            lines.append(line)
    finally:
        shutil.rmtree(prepared[2], ignore_errors=True)
    s = summary(lines)
    print(json.dumps({"summary": s}), flush=True)
    if not args.write_limits:
        return 0
    import jax
    s["control_seeds"] = [ln["seed"] for ln in lines if "control_fp8" in ln]
    spec = set_limits(s, jax.devices()[0].device_kind)
    path = os.path.join(cells.BENCH_ROOT, "chipbench", "cells",
                        args.workload + ".json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(spec, f, indent=1)
        f.write("\n")
    cell = cells.load(args.workload)
    verdicts = {kind: [harness.judge(cell, ln[kind])[0] for ln in lines
                       if kind in ln]
                for kind in ("program", "control_fp8", "fault_half_batch")}
    print(json.dumps({"limits": spec["limits"], "correct": verdicts}),
          flush=True)
    sound = all(verdicts["program"])
    caught = verdicts["control_fp8"] and not any(
        verdicts["control_fp8"] + verdicts["fault_half_batch"])
    return 0 if sound and caught else 1


if __name__ == "__main__":
    sys.exit(main())
