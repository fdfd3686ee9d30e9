"""Where each ``build()`` of a benchmark cell spends its wall, stage by stage.

The benchmark's traced run prints the stages only as sums (``persist`` is
``assemble`` + ``serialize``, ``tail`` is whole). This look runs the cell's
builds as the harness does (``chipbench.run.Fleet``: the same machines from
the seed, the same ``BatchedModelBuilder`` call) with spans on and no
profiler, and prints one JSON line a build: its wall, every
``gordo_build_phase_seconds`` label's seconds in that build, and the bytes of
``model.pkl`` it wrote where the program counts them. Each build's artifacts
are removed as soon as it is read; with ``--keep`` they stay until the end, as
they do in the benchmark's window (and in a deployment), so that a build's
write meets the write-back of the artifacts before it.

    chiprun -- python3 scripts/build_stage_look.py --workload \\
        lfm2_8b_a1b.single_build --seed <n> --builds 4 [--root <checkout>]

``--root`` is the checkout whose ``gordo_tpu`` and ``chipbench`` are run (this
one by default): the parent unpacked beside the change reads the same way. It
needs the chip (``--rehearsal`` with ``--manifest`` for the CPU twin, which
proves the control flow and no number).
"""

import argparse
import json
import os
import shutil
import sys
import tempfile

ARTIFACT_BYTES = "gordo_build_artifact_bytes_total"


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--builds", type=int, default=4)
    parser.add_argument("--root", default=os.path.join(os.path.dirname(__file__), ".."))
    parser.add_argument("--manifest")
    parser.add_argument("--rehearsal", action="store_true")
    parser.add_argument("--keep", action="store_true")
    args = parser.parse_args()
    root = os.path.abspath(args.root)
    sys.path.insert(0, root)
    os.chdir(root)

    from chipbench import run
    from gordo_tpu.observability import telemetry

    cell = run.load_cell(
        args.manifest or os.path.join(root, "BENCHMARK.json"), args.workload
    )
    device = run.set_up(cell, args.rehearsal)[0]
    telemetry.enable_spans()
    out_root = tempfile.mkdtemp(prefix="stage-look-")
    try:
        fleet = run.Fleet(cell, args.seed, out_root)
        # the warm-up build (one chunk) compiles or loads, then the builds read
        sizes = [fleet.traffic.chunk_machines]
        sizes += [fleet.traffic.machines_per_build] * args.builds
        for i, n_machines in enumerate(sizes):
            build = fleet.build(n_machines)
            if not args.keep or i == 0:  # the harness drops its warm-up's too
                shutil.rmtree(build["out_dir"], ignore_errors=True)
            before, after = build["before"], build["after"]
            line = {
                "root": root,
                "device_kind": device.device_kind,
                "build": "warm-up" if i == 0 else i,
                "kept": args.keep,
                "machines": len(build["persisted"]),
                "wall_s": build["end"] - build["start"],
                "ready_s_max": max(build["ready_s"], default=None),
                "compiles": after["compiles"] - before["compiles"],
                "phase_s": {
                    key[len("phase_s."):]: after[key] - before.get(key, 0.0)
                    for key in sorted(after)
                    if key.startswith("phase_s.") and after[key] != before.get(key, 0.0)
                },
            }
            if ARTIFACT_BYTES in after:
                line["artifact_bytes"] = after[ARTIFACT_BYTES] - before.get(ARTIFACT_BYTES, 0)
            print(json.dumps(line), flush=True)
    finally:
        shutil.rmtree(out_root, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
