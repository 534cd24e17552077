#!/usr/bin/env python3
"""Readings of a cell whose configuration has Mamba2 layers, beyond those of
`calibrate.py`: a control of the SSM state's precision, and faults of the
Mamba2 state alone.

    python3 benchmarks/chip/calibrate_hybrid.py --workload <cell> \
        --seeds 1,2,3 [--fault <name>] [--out readings.jsonl]

Each seed is served and read as `calibrate.readings` does. Without
`--fault`, `<number>.control` is the state control: the token that the
float32 reference puts first when it keeps the SSM state in bfloat16 from
the prompt's last position on (the configuration states it in float32),
its reuse-site inputs rounded to the same codes as the reference that
judges it. With `--fault`, one of `FAULTS` is planted in the timed path
and the row holds `<number>.fault`. Each fault touches the Mamba2 state
stack only: the KV cache, the serving state's length, the reuse caches and
the logits are left as the program makes them.
"""

import argparse
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def state_control(family, spec, params, requests, quant, ref=None,
                  ranks=False) -> dict:
    """As `check.control`, with the SSM state carried in bfloat16 in place
    of `quant` codes at the reuse sites."""
    from chip import check, reference

    parts = []
    for r in requests:
        p = len(r.prompt)
        seq = check._sequence(r)
        at = check._at(ref, p)
        h = family.final_hidden(params, spec, seq, quant=at)[p - 1:]
        best, _ = reference.head_max_argmax(params, h)
        hs = family.final_hidden(params, spec, seq, quant=at,
                                 state_from=p - 1)[p - 1:]
        _, pick = reference.head_max_argmax(params, hs)
        parts.append(check._judge(params, h, best, pick, ranks))
    return check._join(parts)


def _with_mamba(state: dict, f) -> dict:
    return {**state, "blocks": {**state["blocks"],
                                "mamba": f(state["blocks"]["mamba"])}}


def mamba_state_reset(sut) -> None:
    """The prefill hands decode a Mamba2 state of zeros: every layer's SSM
    and conv state start over at the first decode step."""
    import jax
    import jax.numpy as jnp

    prefill = sut.prefill

    def reset(p, toks, st):
        logits, st = prefill(p, toks, st)
        return logits, _with_mamba(
            st, lambda m: jax.tree.map(jnp.zeros_like, m))

    sut.prefill = reset


def mamba_state_shifted(sut) -> None:
    """Each decode step writes Mamba2 layer i's new state where layer i + 1
    reads it, and the last layer's where the first reads it."""
    import jax
    import jax.numpy as jnp

    step = sut.decode

    def decode(p, tok, st, rc):
        logits, st, rc = step(p, tok, st, rc)
        return logits, _with_mamba(st, lambda m: jax.tree.map(
            lambda a: jnp.roll(a, 1, axis=0), m)), rc

    sut.decode = decode


FAULTS = {f.__name__: f for f in (mamba_state_reset, mamba_state_shifted)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one wave each")
    ap.add_argument("--fault", default=None, choices=sorted(FAULTS),
                    help="plant this fault in the timed path")
    ap.add_argument("--out", default=None, help="also append rows here")
    args = ap.parse_args(argv)
    sys.path[0:1] = [str(ROOT / "src"), str(ROOT / "benchmarks")]
    from chip import calibrate, check, faults, harness

    cell = harness.load_cell(harness.load_json(ROOT / "BENCHMARK.json"),
                             args.workload, HERE)
    if args.fault is None:
        check.control = state_control
    else:
        faults.FAULTS.update(FAULTS)
    try:
        rows = calibrate.readings(cell, [int(s) for s in args.seeds.split(",")],
                                  out=args.out, fault=args.fault)
    except harness.NoChip as e:
        print(f"not measured: {e}", file=sys.stderr)
        return 3
    print(json.dumps({"workload": cell.name, "fault": args.fault,
                      "control": None if args.fault else "bf16_state",
                      "seeds": len(rows), **calibrate.summary(rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
