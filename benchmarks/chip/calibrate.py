#!/usr/bin/env python3
"""Readings that the check's limits are set from, for one cell.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--fault <name>] [--out readings.jsonl]

In one process, for each seed: draw that seed's weights, serve one wave of
the cell's traffic through the timed path (the traffic generator's own
`run` and `drain`), and read on the same sampled requests every number a
cell may compare (`check.NUMBERS`):

  <number>.program  what a run of the program reads
  <number>.control  the same of the token that the float32 reference puts
                    first when it rounds the reuse-site inputs of decode
                    steps to the configuration's `precision.control_codes`
                    (int4 where the served codes are int8), in the
                    program's place

With `--fault`, a fault of `faults.py` is planted in the timed path for
each seed and the row holds `<number>.fault`, what the broken program reads,
and no control.

The benchmark's own runs never run the control or a fault. A limit lies
above every program reading and below every control reading
(`limits/<cell>.json`).
"""

import argparse
import gc
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def readings(cell, seeds, *, require_chip=True, out=None, fault=None):
    from chip import check, faults, harness, weights
    from chip import system as system_mod

    harness.device_info(cell.chips, require_chip)
    if require_chip:
        harness.enable_persistent_cache()
    model, family = cell.conf["model"], cell.family
    spec = family.spec_of(model)
    control = check.codes(cell.conf, "control_codes")
    sut = params = None
    rows = []
    for seed in seeds:
        params = None              # one seed's weights on the chip at a time
        if sut is not None:
            sut.params = None
        gc.collect()
        params = weights.make_params(family.layout(model), seed)
        if sut is None:
            sut = system_mod.build(cell.conf, family, cell.traffic, params,
                                   check_kernels=require_chip)
            steps = (sut.decode, sut.sample)
        sut.decode, sut.sample = steps
        sut.params = params
        sut.rcache = sut.engine.init_cache(sut.batch) \
            if sut.engine is not None else None
        if fault is not None:
            faults.FAULTS[fault](sut)
        gen = harness.load_module(
            HERE / "generators" / f"{cell.traffic['kind']}.py"
        ).Generator(sut, cell.traffic, seed)
        t0 = time.perf_counter()
        window = gen.run(1e-3)      # one wave, which the drain finishes
        gen.drain(window)
        t1 = time.perf_counter()
        picked = check.sample(window.requests,
                              cell.traffic["check_requests"], seed)
        row = {"workload": cell.name, "seed": seed, "fault": fault,
               "tokens": sum(len(r.tokens) for r in picked),
               "serve_s": t1 - t0}
        for key in {k for k, _, _ in check.NUMBERS.values()}:
            ref = check.codes(cell.conf, key)
            judged = {"fault" if fault else "program":
                      check.served(family, spec, params, picked, ref=ref,
                                   ranks=True)}
            if fault is None:
                judged["control"] = check.control(family, spec, params,
                                                  picked, control, ref=ref,
                                                  ranks=True)
            names = [n for n, v in check.NUMBERS.items() if v[0] == key]
            for side, j in judged.items():
                for n, v in check.numbers_of({key: j}, names).items():
                    row[f"{n}.{side}"] = v
        row["check_s"] = time.perf_counter() - t1
        rows.append(row)
        print(json.dumps(row), flush=True)
        if out is not None:
            with open(out, "a") as f:
                f.write(json.dumps(row) + "\n")
    return rows


def summary(rows) -> dict:
    """The highest program reading and the lowest control or fault reading
    of each number over the seeds."""
    out = {}
    for key in rows[0]:
        name, _, side = key.rpartition(".")
        if name and side in ("program", "control", "fault"):
            f = max if side == "program" else min
            out[key] = f(r[key] for r in rows)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one wave each")
    ap.add_argument("--fault", default=None,
                    help="plant this fault of faults.py in the timed path")
    ap.add_argument("--out", default=None, help="also append rows here")
    args = ap.parse_args(argv)
    sys.path[0:1] = [str(ROOT / "src"), str(ROOT / "benchmarks")]
    from chip import faults, harness

    if args.fault is not None and args.fault not in faults.FAULTS:
        ap.error(f"--fault must be one of {sorted(faults.FAULTS)}")
    cell = harness.load_cell(harness.load_json(ROOT / "BENCHMARK.json"),
                             args.workload, HERE)
    try:
        rows = readings(cell, [int(s) for s in args.seeds.split(",")],
                        out=args.out, fault=args.fault)
    except harness.NoChip as e:
        print(f"not measured: {e}", file=sys.stderr)
        return 3
    print(json.dumps({"workload": cell.name, "fault": args.fault,
                      "seeds": len(rows), **summary(rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
