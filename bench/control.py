"""Readings that set the limits of a cell's check, and its control.

    python3 bench/control.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 1,2,3

For each seed, in one process: the cell's set-up, one query through the
program (the window's first query), and that answer compared with the plain
reference: the sound readings.  For each control seed the plain reference
computed in bfloat16 (``plain.bf16``: bfloat16 operands, float32
accumulation; the step below the float32 the configurations state) takes
the program's place and is compared the same way: the control's readings.
A limit lies above every sound reading and below every control reading.
The benchmark's own runs never run this.  Prints one JSON line per seed,
then ``{"sound_max": ..., "control_min": ...}``; needs the chips the cell
asks for.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import plain  # noqa: E402
import run  # noqa: E402


def readings(name: str, seeds, control_seeds, root=run.ROOT):
    """Yield one dict per seed: the sound and (where asked) control numbers."""
    c = run.load_cell(name, root)
    kind = run.load_module(root / "bench" / "queries" /
                           f"{c['cell']['query']}.py")
    for seed in seeds:
        state = kind.setup(c["config"], c["cell"], seed)
        got = kind.query(state, run.query_seed(seed, 0))
        state.pop("program", None)
        gc.collect()
        want = kind.reference(state, got["seed"])
        out = dict(seed=seed, sound=kind.compare(got, want))
        if seed in control_seeds:
            ctrl = kind.reference(state, got["seed"], rnd=plain.bf16)
            out["control"] = kind.compare(ctrl, want)
        yield out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    ctrl = {int(s) for s in args.control_seeds.split(",") if s}
    problem = run.prepare_jax(run.load_cell(args.workload)["chips"])
    if problem:
        print(f"control: {args.workload}: {problem}", file=sys.stderr)
        return 2
    sound_max, control_min = {}, {}
    for r in readings(args.workload, seeds, ctrl):
        print(json.dumps(r), flush=True)
        for k, v in r["sound"].items():
            sound_max[k] = max(sound_max.get(k, v), v)
        for k, v in r.get("control", {}).items():
            control_min[k] = min(control_min.get(k, v), v)
    print(json.dumps(dict(sound_max=sound_max, control_min=control_min)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
