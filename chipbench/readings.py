"""The readings the limits of ``correct`` are set from, at a cell's own size:

    python -m chipbench.readings --workload <name> --seeds 1,2,3 --out <file>

One process, a line of JSON a seed. For each seed one ``build()`` through
the timed path (one chunk of the cell's machines, or ``--machines``), then
for the sampled machines, each put in the program's place and held to the
cell's own limits (``<mode>_correct``):

``program``     the artifacts against the float32 reference (the lower reading)
``control``     the reference computed in float8, the nearest precision below
                the configuration's bfloat16
``half_batch``  the reference with half of every batch left out
``wrong_slot``  the reference's machines, each in its neighbour's slot; with
                ``one_`` before it only the sample's first machine is at fault
``unchanged``   the initial weights in place of the trained ones (only
                ``weights`` and ``leaf`` read it: both are 1 by construction)
``bfloat16``    the reference with bfloat16 operands (a diagnostic, on request:
                how much of the program's gap is the stated precision itself)

The benchmark's own runs never run this.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

from chipbench import check, reference
from chipbench.run import ROOT, Fleet, load_cell, program_gaps, release_program, sample, set_up


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated")
    parser.add_argument("--out", required=True)
    parser.add_argument("--modes", default="control,half_batch")
    parser.add_argument("--machines", type=int, default=0, help="default: one chunk")
    parser.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    parser.add_argument("--rehearsal", action="store_true")
    args = parser.parse_args(argv)
    cell = load_cell(args.manifest, args.workload)
    config, tr, limits = cell["config"], cell["traffic"], cell["file"]["limits"]
    devices = set_up(cell, args.rehearsal)
    modes = {
        "control": dict(precision="float8"),
        "bfloat16": dict(precision="bfloat16"),
        "half_batch": dict(half_batch=True),
    }
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a") as sink:
        for seed in (int(s) for s in args.seeds.split(",")):
            out_root = tempfile.mkdtemp(prefix="chipbench-readings-")
            try:
                t0 = time.time()
                build = Fleet(cell, seed, out_root).build(args.machines or tr.chunk_machines)
                t_build = time.time() - t0
                names, paths, frames, probe = sample(cell, seed, [build])
                # as a run does before its reference, once a build: it drops
                # every compiled program, the reference's own of the seed
                # before among them, so reference_s holds their reload
                release_program(devices)
                t0 = time.time()
                refs = reference.build_machines(config, names, frames, seed)
                t_ref = time.time() - t0
                line = {
                    "workload": args.workload, "seed": seed, "build_s": t_build,
                    "reference_s": t_ref, "persisted": len(build["persisted"]),
                }

                def record(mode, per_machine):
                    numbers = check.typical(per_machine)
                    line[mode] = numbers
                    line[mode + "_per_machine"] = per_machine
                    line[mode + "_correct"] = check.is_correct(check.verdict(numbers, limits))

                def in_place(others):
                    return [
                        check.gaps(check.as_observed(o, f[probe]), r, f[probe])
                        for o, f, r in zip(others, frames, refs)
                    ]

                record("program", program_gaps(paths, frames, refs, probe))
                for mode in filter(None, args.modes.split(",")):
                    record(mode, in_place(
                        reference.build_machines(config, names, frames, seed, **modes[mode])
                    ))
                shifted = in_place(refs[1:] + refs[:1])
                sound = in_place(refs)
                record("wrong_slot", shifted)
                record("one_wrong_slot", shifted[:1] + sound[1:])
                unchanged = in_place([dict(r, params=r["init"]) for r in refs])
                record("one_unchanged", unchanged[:1] + sound[1:])
                sink.write(json.dumps(line) + "\n")
                sink.flush()
                print(json.dumps(line), file=sys.stderr)
            finally:
                shutil.rmtree(out_root, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
